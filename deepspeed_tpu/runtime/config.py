"""DeepSpeed-compatible JSON config → typed config objects.

TPU-native re-design of the reference config system
(``deepspeed/runtime/config.py`` + ``runtime/config_utils.py`` +
``runtime/zero/config.py``).  A single JSON document (path or dict) with the
same key surface as DeepSpeed produces a ``DeepSpeedConfig`` instance; batch
sizes are resolved with the same divisibility rules
(``train_batch_size == micro_batch * gradient_accumulation_steps * dp_world``).

TPU extensions live under the ``"mesh"`` key (explicit axis sizes) but every
reference key keeps its meaning, so an existing ``ds_config.json`` ports
unchanged.
"""

from __future__ import annotations

import copy
import dataclasses
import json
from dataclasses import dataclass, field, fields
from typing import Any, Dict, List, Optional, Tuple, Union

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


def _filter_kwargs(cls, data: Dict[str, Any], context: str) -> Dict[str, Any]:
    """Keep only keys that are fields of ``cls``; warn about the rest."""
    valid = {f.name for f in fields(cls)}
    out = {}
    for k, v in data.items():
        if k in valid:
            out[k] = v
        else:
            logger.warning(f"Config: ignoring unknown key '{k}' in '{context}'")
    return out


def _from_dict(cls, data: Optional[Dict[str, Any]], context: str):
    data = data or {}
    if not isinstance(data, dict):
        raise DeepSpeedConfigError(f"'{context}' must be a dict, got {type(data)}")
    return cls(**_filter_kwargs(cls, data, context))


@dataclass
class OptimizerConfig:
    """``"optimizer": {"type": ..., "params": {...}}``"""
    type: str = C.ADAMW_OPTIMIZER
    params: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        self.type = self.type.lower()

    @property
    def lr(self) -> float:
        return float(self.params.get("lr", 1e-3))


@dataclass
class SchedulerConfig:
    """``"scheduler": {"type": ..., "params": {...}}``"""
    type: str = "WarmupLR"
    params: Dict[str, Any] = field(default_factory=dict)


@dataclass
class TorchAutocastConfig:
    """Ref: runtime/torch_autocast.py — per-op mixed precision.  Enabling
    selects the compute dtype like bf16/fp16 blocks do, plus two policy
    knobs the model consults per op (models/transformer.py op_fp32):

    * ``fp32_ops``: op classes kept in fp32 (default
      layernorm/softmax/rope/router/loss — the built-in safe set).
      Removing entries is the aggressive full-low-precision mode.
    * ``lower_precision_safe_modules``: module classes ("attn", "mlp")
      allowed in the low dtype; when set, unlisted modules are promoted
      to fp32 (the torch autocast contract)."""
    enabled: bool = False
    dtype: str = "bfloat16"
    fp32_ops: Optional[List[str]] = None
    lower_precision_safe_modules: Optional[List[str]] = None


@dataclass
class FP16Config:
    """Reference: ``runtime/fp16`` config block. ``loss_scale == 0`` means
    dynamic loss scaling (DynamicLossScaler, ref loss_scaler.py:99)."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0
    fp16_master_weights_and_grads: bool = False

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == 0


@dataclass
class BF16Config:
    enabled: bool = False
    immediate_grad_update: bool = True
    check_grad_overflow: bool = False


@dataclass
class OffloadParamConfig:
    """Ref: runtime/zero/offload_config.py (DeepSpeedZeroOffloadParamConfig)."""
    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    max_in_cpu: int = 1_000_000_000
    pin_memory: bool = False


@dataclass
class OffloadOptimizerConfig:
    """Ref: runtime/zero/offload_config.py (DeepSpeedZeroOffloadOptimizerConfig)."""
    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = 1.0  # TwinFlow/Offload++ partial offload fraction
    # SuperOffload (ref engine.py:935 super_offload +
    # superoffload_stage3.py): pipelined host Adam with speculative step +
    # rollback-on-overflow
    super_offload: bool = False
    cpuadam_cores_perc: float = 0.8
    # Chunked host optimizer pipeline (ZeRO-Offload chunked CPU Adam +
    # ZeRO-Infinity NVMe chunk tier; runtime/offload.ChunkedHostOptimizer).
    # working_set_bytes > 0 opts in: when the fp32 optimizer state
    # (12 B/param) exceeds this budget, the Adam step runs on the host over
    # fixed chunk_bytes chunks with double-buffered device↔host streams —
    # peak host residency O(chunk), not O(state).  0 keeps the legacy
    # whole-state streaming/store paths.
    chunk_bytes: int = 64 << 20
    working_set_bytes: int = 0


@dataclass
class ZeroConfig:
    """Ref: ``DeepSpeedZeroConfig`` (runtime/zero/config.py).

    On TPU the stages map to sharding specs over the (data×fsdp) mesh axes:
      stage 0 → replicated params/grads/opt-state (pure DP)
      stage 1 → optimizer state sharded
      stage 2 → optimizer state + gradients sharded (reduce-scatter semantics)
      stage 3 → params also sharded; XLA inserts gather/release collectives
    Bucket-size knobs are accepted for compat; XLA's latency-hiding scheduler
    replaces the IPG bucketing machinery.
    """
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: Optional[OffloadParamConfig] = None
    offload_optimizer: Optional[OffloadOptimizerConfig] = None
    sub_group_size: int = 1_000_000_000
    cpu_offload: Optional[bool] = None  # deprecated alias
    cpu_offload_params: Optional[bool] = None  # deprecated alias
    prefetch_bucket_size: int = 50_000_000
    param_persistence_threshold: int = 100_000
    model_persistence_threshold: int = 2 ** 63 - 1
    max_live_parameters: int = 1_000_000_000
    max_reuse_distance: int = 1_000_000_000
    gather_16bit_weights_on_model_save: bool = False
    use_all_reduce_for_fetch_params: bool = False
    stage3_gather_16bit_weights_on_model_save: Optional[bool] = None
    ignore_unused_parameters: bool = True
    legacy_stage1: bool = False
    round_robin_gradients: bool = False
    # ZeRO++ knobs (ref runtime/zero/config.py:300-313)
    zero_hpz_partition_size: int = 1
    zero_quantized_weights: bool = False
    zero_quantized_nontrainable_weights: bool = False
    zero_quantized_gradients: bool = False
    # MiCS (ref runtime/zero/mics.py)
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    memory_efficient_linear: bool = True
    pipeline_loading_checkpoint: bool = False
    override_module_apply: bool = True
    log_trace_cache_warnings: bool = False
    # TPU extension: fail hard when a >1MB param falls through the
    # divisibility fallback and silently replicates under ZeRO-3/TP
    # (ShardingRules.audit_replicated)
    strict_sharding: bool = False

    def __post_init__(self):
        if isinstance(self.offload_param, dict):
            self.offload_param = _from_dict(OffloadParamConfig, self.offload_param,
                                            "zero_optimization.offload_param")
        if isinstance(self.offload_optimizer, dict):
            self.offload_optimizer = _from_dict(OffloadOptimizerConfig, self.offload_optimizer,
                                                "zero_optimization.offload_optimizer")
        # deprecated aliases from older DeepSpeed configs
        if self.cpu_offload and self.offload_optimizer is None:
            self.offload_optimizer = OffloadOptimizerConfig(device="cpu")
        if self.cpu_offload_params and self.offload_param is None:
            self.offload_param = OffloadParamConfig(device="cpu")
        if self.stage3_gather_16bit_weights_on_model_save is not None:
            self.gather_16bit_weights_on_model_save = self.stage3_gather_16bit_weights_on_model_save
        if not 0 <= self.stage <= 3:
            raise DeepSpeedConfigError(f"zero_optimization.stage must be in [0,3], got {self.stage}")

    @property
    def offload_optimizer_device(self) -> str:
        return self.offload_optimizer.device if self.offload_optimizer else "none"

    @property
    def offload_param_device(self) -> str:
        return self.offload_param.device if self.offload_param else "none"


@dataclass
class ActivationCheckpointingConfig:
    """Ref: runtime/activation_checkpointing/config. On TPU this selects the
    ``jax.checkpoint`` (remat) policy applied to each transformer block.

    ``partition_activations`` needs no dedicated machinery here: the
    reference splits each saved activation across TP ranks by hand
    (checkpointing.py partition_activations) because torch saves full
    replicas per rank; under GSPMD the saved residuals inherit the
    sharding of the computation that produced them (batch/seq/tensor
    axes), so checkpointed activations are already partitioned whenever
    the activations themselves are.  ``cpu_checkpointing``'s analog is
    the ``offload_dots`` remat policy (pinned-host saved residuals)."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU extension: jax remat policy name
    # (full | nothing_saveable | dots_saveable | dots_with_no_batch_dims_saveable | offload_dots)
    remat_policy: str = "nothing_saveable"


@dataclass
class DataEfficiencyConfig:
    """Ref: data_efficiency JSON block (runtime/data_pipeline/config.py):
    curriculum learning under data_sampling, random-LTD under data_routing.
    Legacy top-level ``curriculum_learning`` is also accepted."""
    enabled: bool = False
    seed: int = 1234
    data_sampling: Dict[str, Any] = field(default_factory=dict)
    data_routing: Dict[str, Any] = field(default_factory=dict)

    @property
    def curriculum_config(self) -> Optional[Dict[str, Any]]:
        cl = (self.data_sampling or {}).get("curriculum_learning", {})
        if cl.get("enabled"):
            # single-metric shorthand or per-metric "curriculum_metrics"
            metrics = cl.get("curriculum_metrics")
            if metrics:
                return next(iter(metrics.values()))
            return cl
        return None

    @property
    def random_ltd_config(self) -> Optional[Dict[str, Any]]:
        rl = (self.data_routing or {}).get("random_ltd", {})
        return rl if rl.get("enabled") else None


@dataclass
class MonitorBackendConfig:
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"
    # wandb/comet extras
    team: Optional[str] = None
    group: Optional[str] = None
    project: Optional[str] = None
    experiment_name: Optional[str] = None
    api_key: Optional[str] = None
    workspace: Optional[str] = None
    mode: Optional[str] = None
    samples_log_interval: int = 100


@dataclass
class ProfilerConfig:
    """``"profiler"`` block — windowed XPlane trace capture (the TPU
    analog of the reference's pytorch-profiler integration; see
    utils/trace.py).  The capture brackets train steps
    [start_step, start_step + num_steps)."""
    enabled: bool = False
    output_dir: str = "./dstpu_profile"
    start_step: int = 1
    num_steps: int = 3


@dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    recompute_fwd_factor: float = 0.0
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class TelemetryCaptureConfig:
    """``"telemetry": {"capture": {...}}`` — budgeted XPlane auto-capture
    windows post-processed into overlap reports (telemetry/capture.py)."""
    enabled: bool = False
    capture_step: int = 0          # force a window at this step (0 = off)
    num_steps: int = 1             # steps per capture window
    budget: int = 2                # max captures per process
    regression_factor: float = 0.0  # arm when p95 > k × trailing median
    window: int = 32               # trailing step-time samples consulted
    output_dir: str = "./dstpu_telemetry"
    device_substr: str = "TPU"     # plane filter for the overlap report


@dataclass
class TracingConfig:
    """``"telemetry": {"tracing": {...}}`` — software request/step spans
    (telemetry/tracing.py): host-side monotonic-clock spans exported as
    Chrome trace-event JSON (Perfetto-viewable).  Disabled tracing costs
    one attribute check per span site and allocates nothing."""
    enabled: bool = False
    trace_path: str = ""           # Chrome trace JSON, written at close()
    max_events: int = 100_000      # bounded in-memory event buffer


@dataclass
class FlightConfig:
    """``"telemetry": {"flight": {...}}`` — flight recorder + hang
    watchdog (telemetry/flight.py): a ring of recent span events plus a
    deadline watchdog that dumps all-thread stacks / ring / telemetry
    snapshot bundles on stalls and crashes."""
    enabled: bool = False
    deadline_s: float = 60.0       # no heartbeat for this long => dump
    poll_s: float = 0.0   # watchdog poll (0 = deadline/4, capped at 1s)
    ring_size: int = 2048          # span-event ring capacity
    output_dir: str = "./dstpu_flight"


@dataclass
class TelemetryConfig:
    """``"telemetry"`` block — the unified per-step telemetry layer
    (telemetry/: StepRecord JSONL + Prometheus + monitor bridge +
    auto-capture + span tracing + flight recorder; see
    docs/OBSERVABILITY.md).

    Enabling adds one hard host sync per recorded step (the record needs
    the loss value); ``interval_steps`` thins that cost on TPU — an
    off-interval step skips record assembly (sync included) entirely,
    unless a regression-triggered capture needs every step time.
    ``measure_flops`` pays one extra AOT compile of the train step at
    the first recorded step (exact fused-program FLOPs); set False for
    the free analytic estimate."""
    enabled: bool = False
    run_id: str = ""               # the owning run's identity ("" = none):
    # stamped into every StepRecord, the Tracer's trace metadata, and
    # (via FleetSampler) every TierSnapshot row, so a run's artifacts
    # can be joined back together on it
    jsonl_path: str = ""           # append-only StepRecord log ("" = off)
    prometheus_path: str = ""      # textfile-collector exposition ("" = off)
    interval_steps: int = 1        # record every Nth step
    window: int = 2048             # shared-histogram sliding window
    peak_flops_per_sec: float = 0.0  # MFU denominator (0 = auto-detect)
    measure_flops: bool = True     # profile_compiled; analytic fallback
    capture: TelemetryCaptureConfig = field(
        default_factory=TelemetryCaptureConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    flight: FlightConfig = field(default_factory=FlightConfig)

    def __post_init__(self):
        if isinstance(self.capture, dict):
            self.capture = _from_dict(TelemetryCaptureConfig, self.capture,
                                      "telemetry.capture")
        if isinstance(self.tracing, dict):
            self.tracing = _from_dict(TracingConfig, self.tracing,
                                      "telemetry.tracing")
        if isinstance(self.flight, dict):
            self.flight = _from_dict(FlightConfig, self.flight,
                                     "telemetry.flight")


@dataclass
class RouterServingConfig:
    """``"serving": {"router": {...}}`` — the replica-set front door
    (serving/router.py; docs/SERVING.md "Router & prefix cache"):
    KV-headroom-aware least-loaded dispatch, sticky sessions, fail-over
    with bit-identical greedy continuation."""
    queue_weight: float = 0.05     # score penalty per outstanding request
    max_failovers: int = 2         # re-dispatches before the error sticks
    sticky_sessions: bool = True   # session key -> replica affinity
    max_sessions: int = 4096       # affinity-map bound (oldest evicted)

    def __post_init__(self):
        if self.queue_weight < 0:
            raise DeepSpeedConfigError(
                f"serving.router.queue_weight={self.queue_weight}: "
                "must be >= 0")
        if self.max_failovers < 0:
            raise DeepSpeedConfigError(
                f"serving.router.max_failovers={self.max_failovers}: "
                "must be >= 0")
        if self.max_sessions < 1:
            raise DeepSpeedConfigError(
                f"serving.router.max_sessions={self.max_sessions}: "
                "must be >= 1")


@dataclass
class PrefixCacheServingConfig:
    """``"serving": {"prefix_cache": {...}}`` — paged prefix cache
    (serving/prefix_cache.py): token-block-aligned prompt prefixes map
    to refcounted KV pages, so shared-system-prompt requests adopt
    already-written KV instead of re-prefilling; eviction is LRU over
    cache-only pages under the admission watermarks."""
    enabled: bool = False
    max_blocks: int = 0            # page cap (0 = watermark-bounded only)
    min_prefix_blocks: int = 1     # don't cache prefixes shorter than this

    def __post_init__(self):
        if self.max_blocks < 0:
            raise DeepSpeedConfigError(
                f"serving.prefix_cache.max_blocks={self.max_blocks}: "
                "must be >= 0 (0 = unbounded)")
        if self.min_prefix_blocks < 1:
            raise DeepSpeedConfigError(
                "serving.prefix_cache.min_prefix_blocks="
                f"{self.min_prefix_blocks}: must be >= 1")


@dataclass
class SpeculativeServingConfig:
    """``"serving": {"disagg": {"speculative": {...}}}`` — speculative
    decoding on the decode tier (serving/disagg.py SpeculativeDecoder):
    a draft model in the same serve loop proposes ``spec_k`` tokens per
    sequence, the target verifies them in one ragged step, and greedy
    acceptance is bit-identical to decoding without a draft.  An
    EXTERNAL draft: a second engine, rounds only while every active
    request is opted in and decoding.  A model that publishes a
    multi-token-prediction module drafts for ITSELF instead, inside the
    one ragged step and beside prompts' chunks, with
    ``engine_config["self_draft"]`` and none of these keys
    (docs/SERVING.md)."""
    enabled: bool = False
    draft_model: str = ""          # models.get_model_config name
    spec_k: int = 4                # proposals per sequence per round

    def __post_init__(self):
        if self.spec_k < 1:
            raise DeepSpeedConfigError(
                f"serving.disagg.speculative.spec_k={self.spec_k}: "
                "must be >= 1")
        if self.enabled and not self.draft_model:
            raise DeepSpeedConfigError(
                "serving.disagg.speculative.enabled requires a "
                "draft_model (a models registry name sharing the "
                "target's vocabulary)")


@dataclass
class DisaggServingConfig:
    """``"serving": {"disagg": {...}}`` — disaggregated prefill/decode
    tiers (serving/disagg.py): the first ``prefill_replicas`` device
    slices serve compute-bound prompt legs and hand finished KV chains
    to the ``decode_replicas`` bandwidth-bound slices through the
    refcounted allocator (docs/SERVING.md "Disaggregated tiers")."""
    enabled: bool = False
    prefill_replicas: int = 1
    decode_replicas: int = 1
    speculative: SpeculativeServingConfig = field(
        default_factory=SpeculativeServingConfig)

    def __post_init__(self):
        if isinstance(self.speculative, dict):
            self.speculative = _from_dict(SpeculativeServingConfig,
                                          self.speculative,
                                          "serving.disagg.speculative")
        if self.enabled and (self.prefill_replicas < 1
                             or self.decode_replicas < 1):
            raise DeepSpeedConfigError(
                "serving.disagg needs >= 1 replica per tier, got "
                f"prefill_replicas={self.prefill_replicas} "
                f"decode_replicas={self.decode_replicas}")


@dataclass
class SLOServingConfig:
    """``"serving": {"slo": {...}}`` — latency objectives feeding the
    fleet SLO ledger (telemetry/slo.py; docs/OBSERVABILITY.md "Fleet
    snapshots & SLO ledger"): p95 targets in ms (0 = not targeted), an
    attainment ``objective`` in (0, 1], and per-scenario target
    overrides keyed by bench scenario-mix name.  Consumed by the
    ``serve_disagg``/``serve_load_multi`` bench rows (frozen-key ``slo``
    block) and by ``FleetSampler`` ticks — the PR-19 autoscaler's
    scale-up evidence."""
    enabled: bool = False
    ttft_p95_ms: float = 0.0
    tpot_p95_ms: float = 0.0
    queue_wait_p95_ms: float = 0.0
    objective: float = 0.99
    scenario_overrides: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        # parse through the telemetry-side SLOSpec: ITS validation is
        # the contract (bad objective, unknown override keys), and the
        # round-trip doubles as the drift tripwire for this block
        from deepspeed_tpu.telemetry.slo import SLOSpec
        try:
            parsed = SLOSpec(dict(vars(self)))
        except ValueError as e:
            raise DeepSpeedConfigError(f"serving.slo: {e}") from e
        missing = set(vars(self)) - set(vars(parsed))
        if missing:
            raise DeepSpeedConfigError(
                f"serving.slo keys {sorted(missing)} are not understood "
                "by telemetry.slo.SLOSpec — add them to the telemetry-"
                "side parser in the same commit")


@dataclass
class ServingTierConfig:
    """``"serving"`` block — the multi-replica serving tier: N
    data-parallel replicas on disjoint mesh slices behind one router
    (serving/replica.py + router.py), each with an optional paged
    prefix cache.  ``server_config()``/``router.__dict__`` feed the
    serving classes directly, so the block round-trips into
    ``ReplicaSet.build`` + ``Router`` with no translation layer."""
    n_replicas: int = 1
    metrics_window_s: float = 0.0
    router: RouterServingConfig = field(
        default_factory=RouterServingConfig)
    prefix_cache: PrefixCacheServingConfig = field(
        default_factory=PrefixCacheServingConfig)
    disagg: DisaggServingConfig = field(
        default_factory=DisaggServingConfig)
    slo: SLOServingConfig = field(default_factory=SLOServingConfig)

    def __post_init__(self):
        if isinstance(self.router, dict):
            self.router = _from_dict(RouterServingConfig, self.router,
                                     "serving.router")
        if isinstance(self.prefix_cache, dict):
            self.prefix_cache = _from_dict(PrefixCacheServingConfig,
                                           self.prefix_cache,
                                           "serving.prefix_cache")
        if isinstance(self.disagg, dict):
            self.disagg = _from_dict(DisaggServingConfig, self.disagg,
                                     "serving.disagg")
        if isinstance(self.slo, dict):
            self.slo = _from_dict(SLOServingConfig, self.slo,
                                  "serving.slo")
        if self.n_replicas < 1:
            raise DeepSpeedConfigError(
                f"serving.n_replicas={self.n_replicas}: must be >= 1")
        if self.metrics_window_s < 0:
            raise DeepSpeedConfigError(
                f"serving.metrics_window_s={self.metrics_window_s}: "
                "must be >= 0 (0 = lifetime window)")
        if self.disagg.enabled:
            want = (self.disagg.prefill_replicas
                    + self.disagg.decode_replicas)
            if want != self.n_replicas:
                raise DeepSpeedConfigError(
                    f"serving.disagg tiers ({self.disagg.prefill_replicas}"
                    f" prefill + {self.disagg.decode_replicas} decode = "
                    f"{want}) must sum to serving.n_replicas="
                    f"{self.n_replicas}")
        # drift tripwire: the serving-side parsers (serving/router.py
        # RouterConfig, serving/prefix_cache.py PrefixCacheConfig,
        # serving/disagg.py DisaggConfig) accept these dicts and silently
        # IGNORE unknown keys — a field added here but not there would
        # validate at config load and then be dropped at runtime.
        # Round-trip through them and require every block key to come
        # back as an attribute.
        from deepspeed_tpu.serving.disagg import DisaggConfig
        from deepspeed_tpu.serving.prefix_cache import PrefixCacheConfig
        from deepspeed_tpu.serving.router import RouterConfig
        for block, cls in ((self.router_config(), RouterConfig),
                           (self.prefix_cache_config(), PrefixCacheConfig),
                           (self.disagg_config(), DisaggConfig)):
            parsed = cls(block)
            missing = set(block) - set(vars(parsed))
            if missing:
                raise DeepSpeedConfigError(
                    f"serving config keys {sorted(missing)} are not "
                    f"understood by {cls.__name__} — add them to the "
                    "serving-side parser in the same commit")
        # ...and one level deeper for the nested speculative block
        from deepspeed_tpu.serving.disagg import SpeculativeConfig
        spec_block = dict(vars(self.disagg.speculative))
        spec_missing = set(spec_block) - set(vars(
            SpeculativeConfig(spec_block)))
        if spec_missing:
            raise DeepSpeedConfigError(
                f"serving.disagg.speculative keys {sorted(spec_missing)} "
                "are not understood by SpeculativeConfig — add them to "
                "the serving-side parser in the same commit")

    def prefix_cache_config(self) -> Dict[str, Any]:
        """Per-replica prefix-cache config dict."""
        return dict(vars(self.prefix_cache))

    def server_config(self) -> Dict[str, Any]:
        """Per-replica ``InferenceServer`` config dict."""
        return {"prefix_cache": self.prefix_cache_config(),
                "metrics_window_s": self.metrics_window_s}

    def router_config(self) -> Dict[str, Any]:
        """``Router`` config dict."""
        return dict(vars(self.router))

    def disagg_config(self) -> Dict[str, Any]:
        """``serving.disagg`` dict for ``ReplicaSet.build(disagg=...)``
        (the nested speculative block flattens to a plain dict so the
        serving-side ``DisaggConfig`` can re-parse it)."""
        d = dict(vars(self.disagg))
        d["speculative"] = dict(vars(self.disagg.speculative))
        return d

    def slo_config(self) -> Dict[str, Any]:
        """``serving.slo`` dict for ``telemetry.slo.SLOSpec``."""
        return dict(vars(self.slo))


@dataclass
class StepScheduleConfig:
    """``"step_schedule"`` block — the overlap-driven step schedule
    (autotuning/overlap_scheduler.py; docs/AUTOTUNING.md).

    ``mode``:

    * ``"static"`` — the defaults below (or explicit values) apply as-is;
      no probing.
    * ``"probe"`` — a launch path that honors the block (bench rows,
      ``ensure_schedule``) runs ``probe_steps`` compiled steps under a
      forced telemetry capture, reads the overlap report, and rewrites
      the block to ``"pinned"`` with the chosen knobs.
    * ``"pinned"`` — a tuned schedule frozen by a previous probe; never
      re-probes, so a tuned run is reproducible.  ``decisions`` carries
      the :class:`ScheduleDecision` records (evidence included) that
      justified the pinned values.

    Knob families (each actuated by ``runtime/engine.py``):

    * ``gather_prefetch_depth`` — ZeRO-3 gather prefetch window: the
      layer-scan unroll factor, which bounds how far XLA's
      latency-hiding scheduler can hoist a parameter all-gather ahead of
      its use (models/transformer.py ``scan_unroll``).
    * ``param_persistence_threshold`` / ``prefetch_bucket_size`` —
      overrides for the static ``zero_optimization`` values (``None`` =
      keep the zero block's setting).  The persistence threshold feeds
      the sharding rules directly (small ZeRO-3 params stay gathered).
    * ``ring_interleave`` — ring-attention hop schedule: 1 = attend then
      rotate (serial), 2 = issue the next hop's ppermute before the
      attend so transfer and compute are dataflow-independent
      (sequence/ring.py).
    * ``weight_update`` — ``"fused"`` (the stage's native layout) or
      ``"decomposed"`` (shard optimizer state + grad accumulator over
      the ZeRO axes even at stage 0/1: reduce-scatter + 1/world update +
      params all-gather, arXiv:2004.13336).
    * ``fused_gather_matmul`` — ZeRO-3 fused gather-matmul
      (ops/pallas/gather_matmul.py): the layer MLP runs as an explicit
      shard_map whose matmul region issues the following matmul's param
      all-gather ahead of the current one, instead of leaving the
      gathers to GSPMD scheduling (the T3 fusion, arXiv:2401.16677);
      composes with ``gather_prefetch_depth``'s unroll window.
      Warn-fallback to the scheduled path when the config is ineligible.
    * ``fused_reduce_scatter`` — with ``weight_update="decomposed"``,
      the train step accumulates gradients LOCALLY inside a shard_map
      over the DP axes and issues an explicit per-leaf reduce-scatter in
      the accumulation epilogue, consuming the accumulator in place,
      instead of relying on GSPMD to insert the scatter at the layout
      constraint.  Warn-fallback when ineligible.
    """
    mode: str = "static"            # static | probe | pinned
    probe_steps: int = 3            # compiled steps per probe (+1 warmup)
    overlap_threshold: float = 0.5  # overlap below this ⇒ act
    gather_prefetch_depth: int = 1
    param_persistence_threshold: Optional[int] = None
    prefetch_bucket_size: Optional[int] = None
    ring_interleave: int = 1
    weight_update: str = "fused"    # fused | decomposed
    fused_gather_matmul: bool = False
    fused_reduce_scatter: bool = False
    decisions: Optional[List[Dict[str, Any]]] = None

    MODES = ("static", "probe", "pinned")
    WEIGHT_UPDATES = ("fused", "decomposed")
    RING_INTERLEAVES = (1, 2)

    def __post_init__(self):
        if self.mode not in self.MODES:
            raise DeepSpeedConfigError(
                f"step_schedule.mode must be one of {list(self.MODES)}, "
                f"got {self.mode!r}")
        if self.weight_update not in self.WEIGHT_UPDATES:
            raise DeepSpeedConfigError(
                f"step_schedule.weight_update must be one of "
                f"{list(self.WEIGHT_UPDATES)}, got {self.weight_update!r}")
        if int(self.ring_interleave) not in self.RING_INTERLEAVES:
            raise DeepSpeedConfigError(
                f"step_schedule.ring_interleave must be one of "
                f"{list(self.RING_INTERLEAVES)}, got {self.ring_interleave}")
        self.ring_interleave = int(self.ring_interleave)
        self.fused_gather_matmul = bool(self.fused_gather_matmul)
        self.fused_reduce_scatter = bool(self.fused_reduce_scatter)
        if int(self.probe_steps) < 1:
            raise DeepSpeedConfigError(
                f"step_schedule.probe_steps must be >= 1, got "
                f"{self.probe_steps}")
        self.probe_steps = int(self.probe_steps)
        if int(self.gather_prefetch_depth) < 1:
            raise DeepSpeedConfigError(
                "step_schedule.gather_prefetch_depth must be >= 1, got "
                f"{self.gather_prefetch_depth}")
        self.gather_prefetch_depth = int(self.gather_prefetch_depth)
        if not 0.0 <= float(self.overlap_threshold) <= 1.0:
            raise DeepSpeedConfigError(
                "step_schedule.overlap_threshold must be in [0, 1], got "
                f"{self.overlap_threshold}")
        if self.param_persistence_threshold is not None:
            if int(self.param_persistence_threshold) < 0:
                raise DeepSpeedConfigError(
                    "step_schedule.param_persistence_threshold must be >= 0")
            self.param_persistence_threshold = \
                int(self.param_persistence_threshold)
        if self.prefetch_bucket_size is not None:
            if int(self.prefetch_bucket_size) <= 0:
                raise DeepSpeedConfigError(
                    "step_schedule.prefetch_bucket_size must be positive")
            self.prefetch_bucket_size = int(self.prefetch_bucket_size)
        if self.decisions is not None:
            # decision records round-trip through the frozen vocabulary —
            # a hand-edited pinned block with a bogus decision fails at
            # config load, not at some later analysis step
            from deepspeed_tpu.autotuning.overlap_scheduler import \
                ScheduleDecision

            try:
                for d in self.decisions:
                    ScheduleDecision.from_dict(d)
            except (KeyError, TypeError, ValueError) as e:
                raise DeepSpeedConfigError(
                    f"step_schedule.decisions: invalid record ({e})") from e


@dataclass
class CommQuantizationConfig:
    """``"comm_quantization"`` block — quantized ZeRO collectives
    (comm/quantized.py; docs/QUANTIZED_COMM.md).

    Selects a wire dtype per collective:

    * ``grad_reduce`` — the data-parallel gradient reduction of the
      train step.  Any non-default setting (including explicit
      ``"fp32"``) routes the reduction through the engine's explicit
      shard_map collective path, whose wire volume is recorded
      per-collective in telemetry; ``int8``/``fp8`` quantize the
      payload (EQuARX-style block scaling, fp32 accumulation).
    * ``zero3_gather`` — the stage-3 parameter all-gather (the qwZ
      straight-through gather, parallel/zeropp.py); ``int8``/``fp8``
      move quantized payloads on the wire.
    * ``ring_rotation`` — the ring-attention K/V (and traveling-grad)
      rotation over the "seq" mesh ring (sequence/ring.py):
      ``int8``/``fp8`` move block-quantized payloads + per-row fp32
      scales on every ``ppermute`` hop; dequant runs inside the
      consuming flash kernel's epilogue on the fused path (int8) or
      through the shared XLA codec otherwise.  Blocks are the head dim
      (``group_size`` does not apply to this collective).

    ``error_feedback`` carries the grad-reduce quantization residual
    into the next step (LoCo-style; ignored for fp32 wire).  The
    ``collectives`` dict is an equivalent per-collective spelling
    (``{"grad_reduce": "int8"}``); unknown collective names are
    rejected."""
    enabled: bool = False
    grad_reduce: str = "fp32"      # fp32 | int8 | fp8
    zero3_gather: str = "fp32"     # fp32 | int8 | fp8
    ring_rotation: str = "fp32"    # fp32 | int8 | fp8
    group_size: int = 256          # block size per fp32 scale
    error_feedback: bool = True
    collectives: Optional[Dict[str, str]] = None

    COLLECTIVES = ("grad_reduce", "zero3_gather", "ring_rotation")
    WIRE_DTYPES = ("fp32", "int8", "fp8")

    def __post_init__(self):
        if self.collectives is not None:
            if not isinstance(self.collectives, dict):
                raise DeepSpeedConfigError(
                    "comm_quantization.collectives must be a dict of "
                    "{collective: wire_dtype}")
            for name, dtype in self.collectives.items():
                if name not in self.COLLECTIVES:
                    raise DeepSpeedConfigError(
                        f"comm_quantization.collectives: unknown collective "
                        f"{name!r} (known: {list(self.COLLECTIVES)})")
                setattr(self, name, dtype)
        for name in self.COLLECTIVES:
            val = str(getattr(self, name)).lower()
            if val not in self.WIRE_DTYPES:
                raise DeepSpeedConfigError(
                    f"comm_quantization.{name} must be one of "
                    f"{list(self.WIRE_DTYPES)}, got {val!r}")
            setattr(self, name, val)
        if int(self.group_size) <= 0:
            raise DeepSpeedConfigError(
                f"comm_quantization.group_size must be positive, got "
                f"{self.group_size}")
        self.group_size = int(self.group_size)


@dataclass
class CommsLoggerConfig:
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)


@dataclass
class TensorParallelConfig:
    """Ref: runtime/tensor_parallel config + AutoTP. ``autotp_size`` sets the
    mesh "tensor" axis; sharding rules come from the model's param-path
    patterns (AutoTP-equivalent, module_inject/auto_tp.py:193)."""
    enabled: bool = True
    autotp_size: int = 1
    tp_size: Optional[int] = None
    tp_grain_size: int = 64

    @property
    def size(self) -> int:
        return int(self.tp_size or self.autotp_size or 1)


@dataclass
class PipelineConfig:
    """Ref: runtime/pipe. ``stages`` sets the mesh "pipe" axis."""
    stages: int = 1
    partition_method: str = "parameters"
    activation_checkpoint_interval: int = 0
    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    num_microbatches: Optional[int] = None


@dataclass
class MeshConfig:
    """TPU extension: explicit logical mesh axis sizes.

    Any axis set to -1 is inferred so the product equals the device count.
    Axis semantics (outer→inner, DCN-friendly axes first):
      pipe   — pipeline stages            (ref: runtime/pipe/topology.py)
      data   — pure data parallel / ZeRO  (ref: DP groups, groups.py)
      expert — expert parallel subdivision of data (ref: groups.py:240)
      seq    — Ulysses sequence parallel  (ref: sequence/layer.py)
      tensor — tensor/model parallel      (ref: AutoTP)
    """
    pipe: int = 1
    data: int = -1
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def sizes(self) -> Dict[str, int]:
        return {"pipe": self.pipe, "data": self.data, "expert": self.expert,
                "seq": self.seq, "tensor": self.tensor}

    def resolved(self, n_devices: int) -> Dict[str, int]:
        """Delegates to the topology resolver so config and MeshTopology can
        never disagree on mesh semantics."""
        from deepspeed_tpu.parallel.topology import resolve_mesh_sizes

        try:
            return resolve_mesh_sizes(self.sizes(), n_devices)
        except ValueError as e:
            raise DeepSpeedConfigError(str(e)) from e


@dataclass
class CheckpointConfig:
    """Ref: runtime/config checkpoint block + checkpoint_engine selection."""
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    async_save: bool = False
    writer: Optional[Dict[str, Any]] = None


@dataclass
class AIOConfig:
    """Ref: op_builder/async_io.py + deepspeed/runtime/swap_tensor/constants.py."""
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True
    use_gds: bool = False
    use_direct: bool = False  # O_DIRECT data path (bypass the page cache)


class DeepSpeedConfig:
    """Parsed + validated config. Accepts a JSON path or a dict.

    Ref: ``DeepSpeedConfig`` (runtime/config.py). ``world_size`` here is the
    *data-parallel* world (dp×expert axes), used for batch resolution exactly
    like the reference's ``dp_world_size``.
    """

    def __init__(self, config: Union[str, Dict[str, Any], None],
                 world_size: Optional[int] = 1,
                 n_devices: Optional[int] = None):
        if config is None:
            config = {}
        if isinstance(config, str):
            with open(config, "r") as f:
                config = json.load(f)
        if not isinstance(config, dict):
            raise DeepSpeedConfigError(f"config must be a dict or JSON path, got {type(config)}")
        self._param_dict = copy.deepcopy(config)
        self.world_size = world_size

        d = self._param_dict
        # -- batch sizes (resolved below) --
        self.train_batch_size: Optional[int] = d.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu: Optional[int] = d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps: Optional[int] = d.get(C.GRADIENT_ACCUMULATION_STEPS)

        # -- sub-configs --
        opt = d.get(C.OPTIMIZER)
        self.optimizer: Optional[OptimizerConfig] = (
            _from_dict(OptimizerConfig, opt, "optimizer") if opt is not None else None)
        sched = d.get(C.SCHEDULER)
        self.scheduler: Optional[SchedulerConfig] = (
            _from_dict(SchedulerConfig, sched, "scheduler") if sched is not None else None)
        self.fp16 = _from_dict(FP16Config, d.get(C.FP16), "fp16")
        bf16_dict = d.get(C.BFLOAT16, d.get(C.BFLOAT16_OLD))
        self.bf16 = _from_dict(BF16Config, bf16_dict, "bf16")
        self.torch_autocast = _from_dict(TorchAutocastConfig,
                                         d.get("torch_autocast"),
                                         "torch_autocast")
        if self.torch_autocast.enabled:
            if self.fp16.enabled or self.bf16.enabled:
                raise DeepSpeedConfigError(
                    "torch_autocast cannot be combined with an explicit "
                    "fp16/bf16 block (ref runtime/torch_autocast.py)")
            # autocast selects the compute dtype (per-op fp32 islands are
            # the built-in policy of the functional model)
            dt = self.torch_autocast.dtype
            if dt in ("bfloat16", "bf16"):
                self.bf16 = BF16Config(enabled=True)
            elif dt in ("float16", "fp16", "half"):
                self.fp16 = _from_dict(FP16Config, {"enabled": True},
                                       "fp16")
            else:
                raise DeepSpeedConfigError(
                    f"torch_autocast.dtype must be bfloat16 or float16, "
                    f"got {dt!r}")
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        self.zero_config = _from_dict(ZeroConfig, d.get(C.ZERO_OPTIMIZATION), "zero_optimization")
        self.activation_checkpointing = _from_dict(
            ActivationCheckpointingConfig, d.get(C.ACTIVATION_CHECKPOINTING), "activation_checkpointing")
        self.tensorboard = _from_dict(MonitorBackendConfig, d.get(C.TENSORBOARD), "tensorboard")
        self.wandb = _from_dict(MonitorBackendConfig, d.get(C.WANDB), "wandb")
        self.csv_monitor = _from_dict(MonitorBackendConfig, d.get(C.CSV_MONITOR), "csv_monitor")
        self.comet = _from_dict(MonitorBackendConfig, d.get(C.COMET), "comet")
        self.flops_profiler = _from_dict(FlopsProfilerConfig, d.get(C.FLOPS_PROFILER), "flops_profiler")
        self.profiler = _from_dict(ProfilerConfig, d.get(C.PROFILER), "profiler")
        self.comms_logger = _from_dict(CommsLoggerConfig, d.get(C.COMMS_LOGGER), "comms_logger")
        self.comm_quantization = _from_dict(
            CommQuantizationConfig, d.get("comm_quantization"),
            "comm_quantization")
        self.step_schedule = _from_dict(
            StepScheduleConfig, d.get("step_schedule"), "step_schedule")
        self.telemetry = _from_dict(TelemetryConfig, d.get(C.TELEMETRY), "telemetry")
        self.serving = _from_dict(ServingTierConfig, d.get("serving"),
                                  "serving")
        self.tensor_parallel = _from_dict(TensorParallelConfig, d.get(C.TENSOR_PARALLEL), "tensor_parallel")
        self.pipeline = _from_dict(PipelineConfig, d.get(C.PIPELINE), "pipeline")
        self.checkpoint_config = _from_dict(CheckpointConfig, d.get(C.CHECKPOINT), "checkpoint")
        self.aio_config = _from_dict(AIOConfig, d.get("aio"), "aio")
        de = d.get(C.DATA_EFFICIENCY)
        if de is None and d.get(C.CURRICULUM_LEARNING_LEGACY, {}).get("enabled"):
            # legacy top-level curriculum_learning block → wrap it
            de = {"enabled": True,
                  "data_sampling": {"curriculum_learning":
                                    d[C.CURRICULUM_LEARNING_LEGACY]}}
        self.data_efficiency = _from_dict(DataEfficiencyConfig, de,
                                          "data_efficiency")

        # -- mesh --
        mesh_dict = dict(d.get(C.MESH) or {})
        if "tensor" not in mesh_dict and self.tensor_parallel.size > 1:
            mesh_dict["tensor"] = self.tensor_parallel.size
        if "seq" not in mesh_dict and d.get(C.SEQUENCE_PARALLEL_SIZE):
            mesh_dict["seq"] = int(d[C.SEQUENCE_PARALLEL_SIZE])
        if "pipe" not in mesh_dict and self.pipeline.stages > 1:
            mesh_dict["pipe"] = self.pipeline.stages
        if "expert" not in mesh_dict and d.get(C.EXPERT_PARALLEL_SIZE):
            mesh_dict["expert"] = int(d[C.EXPERT_PARALLEL_SIZE])
        self.mesh = _from_dict(MeshConfig, mesh_dict, "mesh")

        # -- scalars --
        self.gradient_clipping: float = float(d.get(C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT))
        self.prescale_gradients: bool = bool(d.get(C.PRESCALE_GRADIENTS, C.PRESCALE_GRADIENTS_DEFAULT))
        self.gradient_predivide_factor: float = float(
            d.get(C.GRADIENT_PREDIVIDE_FACTOR, C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT))
        self.steps_per_print: int = int(d.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT))
        self.wall_clock_breakdown: bool = bool(d.get(C.WALL_CLOCK_BREAKDOWN, False))
        self.memory_breakdown: bool = bool(d.get(C.MEMORY_BREAKDOWN, False))
        self.dump_state: bool = bool(d.get(C.DUMP_STATE, False))
        self.zero_allow_untested_optimizer: bool = bool(d.get(C.ZERO_ALLOW_UNTESTED_OPTIMIZER, False))
        self.communication_data_type: Optional[str] = d.get(C.COMMUNICATION_DATA_TYPE)
        self.sparse_gradients_enabled: bool = bool(d.get(C.SPARSE_GRADIENTS, False))
        self.load_universal_checkpoint: bool = bool(
            d.get(C.LOAD_UNIVERSAL_CHECKPOINT, self.checkpoint_config.load_universal))
        self.dataloader_drop_last: bool = bool(d.get(C.DATALOADER_DROP_LAST, False))
        self.seed: int = int(d.get("seed", 42))
        self.gradient_accumulation_dtype: str = d.get("data_types", {}).get(
            "grad_accum_dtype", "fp32") if isinstance(d.get("data_types"), dict) else "fp32"

        # world_size=None defers batch resolution until the topology is known
        # (engine calls resolve_world()).
        if world_size is not None:
            self._resolve_batch_sizes()

    def resolve_world(self, world_size: int) -> None:
        """Set the data-parallel world and resolve batch sizes (deferred)."""
        self.world_size = world_size
        self._resolve_batch_sizes()

    # ------------------------------------------------------------------
    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def _resolve_batch_sizes(self) -> None:
        """Same resolution rules as ref runtime/config.py batch assertions:
        train == micro * gas * dp_world; any one may be inferred."""
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        ws = max(1, self.world_size)

        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            if train % (micro * ws) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {train} not divisible by micro_batch*world {micro * ws}")
            gas = train // (micro * ws)
        elif train is not None and gas is not None:
            if train % (gas * ws) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {train} not divisible by gas*world {gas * ws}")
            micro = train // (gas * ws)
        elif micro is not None:
            gas = gas or C.GRADIENT_ACCUMULATION_STEPS_DEFAULT
            train = micro * gas * ws
        elif train is not None:
            gas = 1
            if train % ws != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {train} not divisible by world size {ws}")
            micro = train // ws
        else:
            raise DeepSpeedConfigError(
                "At least one of train_batch_size / train_micro_batch_size_per_gpu must be set")

        if train != micro * gas * ws:
            raise DeepSpeedConfigError(
                f"Inconsistent batch config: train_batch_size={train} != "
                f"micro({micro}) * gas({gas}) * dp_world({ws})")
        self.train_batch_size = int(train)
        self.train_micro_batch_size_per_gpu = int(micro)
        self.gradient_accumulation_steps = int(gas)

    def to_dict(self) -> Dict[str, Any]:
        return copy.deepcopy(self._param_dict)

    def __repr__(self) -> str:  # pragma: no cover
        parts = [f"train_batch_size={self.train_batch_size}",
                 f"micro={self.train_micro_batch_size_per_gpu}",
                 f"gas={self.gradient_accumulation_steps}",
                 f"zero_stage={self.zero_config.stage}",
                 f"bf16={self.bf16.enabled}", f"fp16={self.fp16.enabled}"]
        return "DeepSpeedConfig(" + ", ".join(parts) + ")"


def load_plan(plan: Union[str, Dict[str, Any]],
              world_size: Optional[int] = 1,
              rank: int = 0) -> "DeepSpeedConfig":
    """Load a planner-emitted plan file (``dstpu-plan --json``) as a
    validated ``DeepSpeedConfig`` — the round-trip half of the plan
    contract (docs/PLANNER.md "Plan files"): the ``rank``-th ranked
    entry's config fragment parses with no edits, or this raises
    ``DeepSpeedConfigError``.  Accepts a path, a plan dict, or a bare
    config fragment (a dict without a ``ranked`` list)."""
    if isinstance(plan, str):
        with open(plan, "r") as f:
            plan = json.load(f)
    if not isinstance(plan, dict):
        raise DeepSpeedConfigError(
            f"plan must be a dict or JSON path, got {type(plan)}")
    if "ranked" in plan:
        ranked = plan["ranked"]
        if not ranked:
            raise DeepSpeedConfigError("plan ranked no candidates")
        if not 0 <= rank < len(ranked):
            raise DeepSpeedConfigError(
                f"plan has {len(ranked)} ranked entries; no rank {rank}")
        fragment = ranked[rank]["config"]
    else:
        fragment = plan
    return DeepSpeedConfig(copy.deepcopy(fragment),
                           world_size=world_size)
