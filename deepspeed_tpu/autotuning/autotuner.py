"""Autotuner — memory-model-driven search over ZeRO stage & micro-batch.

Analog of ``deepspeed/autotuning/autotuner.py`` (``Autotuner`` :42,
``model_info_profile_run`` :663, ``get_instantiation_memory_required_per_gpu``
:278) and the grid/random/model-based tuners (``autotuning/tuner/``).  The
reference launches whole subprocess experiment jobs; on TPU a trial is just
building an engine and timing a few compiled steps in-process — rendezvous
and relaunch overhead don't exist under single-controller JAX.

Flow (mirrors Autotuner.tune): estimate per-device memory for each ZeRO
stage → prune stages that can't fit → sweep micro-batch sizes (power-of-2
"model-based" ordering) → run short timed trials → pick best throughput.

Caveat (trial fidelity): trials time the CURRENT backend.  On a real TPU
the ranking is authoritative; on the virtual CPU mesh (CI) the
memory-model pruning is still sound, but the throughput
ORDERING reflects the CPU interpreter's cost model, not the chip's — MXU
tiling, ICI bandwidth, and HBM pressure differences do not register.
Treat CPU-mesh tuning results as feasibility screening and re-run the
final sweep on hardware (``bin/dstpu_autotune`` on the pod) before
committing a launch config.
"""

from __future__ import annotations

import copy
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu.utils.logging import logger

BYTES_PER_PARAM = {"bf16": 2, "fp16": 2, "fp32": 4}


@dataclass
class ModelInfo:
    """Ref model_info_profile_run: num_params + activation footprint."""
    num_params: int
    hidden_size: int = 0
    num_layers: int = 0
    vocab_size: int = 0


def estimate_memory_breakdown(model_info: ModelInfo, zero_stage: int,
                              dp_size: int, micro_batch: int, seq_len: int,
                              dtype: str = "bf16",
                              optimizer_factor: int = 12,
                              tp_size: int = 1, pp_size: int = 1,
                              sp_size: int = 1,
                              comm_quant: bool = False,
                              comm_group_size: int = 256) -> Dict[str, int]:
    """Per-class bytes per device for params/grads/optimizer/activations/
    logits/comm (+ ``total``) — the ladder predictor reports WHICH class
    blew the budget, not just that it did.

    Ref get_instantiation_memory_required_per_gpu (autotuner.py:278):
    optimizer_factor=12 ≈ fp32 master + two Adam moments + fp16 param/grad
    bookkeeping, partitioned by stage:
      stage 0: all replicated; 1: optimizer/dp; 2: +grads/dp; 3: +params/dp.
    Model-parallel axes shard everything multiplicatively: tensor/pipe split
    params+grads+optimizer; pipe splits resident layers (activations too);
    seq splits the activation sequence dim.

    ``comm_quant`` prices the comm-quantization error-feedback residual:
    the engine rides a ``[world, padded]`` fp32 buffer through the step
    signature (engine.py, quantized-DP grad reduce), sharded over the DP
    axis — per device that is ``padded * 4`` bytes where ``padded`` rounds
    the flat param count up to a multiple of ``world * group_size``, i.e.
    ~4 bytes/param REGARDLESS of dp_size.  It only materializes on the
    eligible path (dp > 1, pure-DP mesh, stage <= 2), matching the
    engine's fallback gate.
    """
    p = model_info.num_params // max(1, tp_size * pp_size)
    b = BYTES_PER_PARAM.get(dtype, 2)
    params_mem = p * b
    grads_mem = p * b
    opt_mem = p * optimizer_factor
    if zero_stage >= 1:
        opt_mem //= dp_size
    if zero_stage >= 2:
        grads_mem //= dp_size
    if zero_stage >= 3:
        params_mem //= dp_size
    # activation estimate: ~ layers * micro_batch * seq * hidden * c bytes.
    # NOT divided by pp: the 1F1B schedule keeps O(pp) microbatches in
    # flight, cancelling the layers/pp split per stage.
    act = (model_info.num_layers * micro_batch * seq_len
           * max(1, model_info.hidden_size) * 2 * 16
           // max(1, sp_size * tp_size))
    # fp32 [B, S, V] logits + their cotangent: dominates small models with
    # big vocabs (r04 on-chip validation: the estimator passed gpt2-125m
    # mb=64 at 11.6GB est but the 6.6GB logits buffer OOM'd the
    # trial).  Sequence-tiled loss (loss_tiles) avoids the
    # buffer, but the tuner prices the default untiled path.
    logits = (micro_batch * seq_len * max(1, model_info.vocab_size) * 4 * 2
              // max(1, sp_size * tp_size))
    comm_mem = 0
    if (comm_quant and dp_size > 1 and zero_stage <= 2
            and tp_size == 1 and pp_size == 1 and sp_size == 1):
        base = dp_size * max(1, comm_group_size)
        padded = -(-model_info.num_params // base) * base
        comm_mem = padded * 4  # fp32 EF residual row per device
    out = {"params": int(params_mem), "grads": int(grads_mem),
           "optimizer": int(opt_mem), "activations": int(act),
           "logits": int(logits), "comm": int(comm_mem)}
    out["total"] = sum(out.values())
    return out


def estimate_memory_per_device(model_info: ModelInfo, zero_stage: int,
                               dp_size: int, micro_batch: int, seq_len: int,
                               dtype: str = "bf16",
                               optimizer_factor: int = 12,
                               tp_size: int = 1, pp_size: int = 1,
                               sp_size: int = 1) -> int:
    """Total bytes per device (see :func:`estimate_memory_breakdown`)."""
    return estimate_memory_breakdown(
        model_info, zero_stage, dp_size, micro_batch, seq_len, dtype,
        optimizer_factor, tp_size, pp_size, sp_size)["total"]


def load_memory_calibration(path: Optional[str] = None,
                            backend: str = "cpu") -> float:
    """The ``model_drift`` calibration ratio (XLA-measured static peak /
    analytic estimate) the memory auditor froze into
    ``tools/memory_baseline.json`` for ``backend`` — 1.0 when the file
    or the backend entry is absent.  Multiplying the analytic estimate
    by this ratio turns the never-validated model into one anchored to
    what XLA actually allocates on this backend."""
    import json

    if path is None:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "tools", "memory_baseline.json")
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return 1.0
    try:
        return float(data.get("calibration", {}).get(backend, 1.0)) or 1.0
    except (TypeError, ValueError):
        return 1.0


def predict_fit(model_info: ModelInfo, zero_stage: int, dp_size: int,
                micro_batch: int, seq_len: int, hbm_bytes: int,
                dtype: str = "bf16", calibration: float = 1.0,
                tp_size: int = 1, pp_size: int = 1, sp_size: int = 1,
                offload_param: Optional[str] = None,
                offload_optimizer: Optional[str] = None,
                host_bytes: Optional[int] = None,
                chunk_bytes: Optional[int] = None,
                comm_quant: bool = False,
                comm_group_size: int = 256) -> Dict[str, Any]:
    """The OOM-before-you-run gate: calibrated per-device peak estimate
    vs the device budget, with the dominant class and shortfall when it
    does NOT fit — so a too-big ladder rung reports *why* instead of
    dying in RESOURCE_EXHAUSTED.

    ZeRO-Offload re-homes whole classes off the device
    (``offload_param`` / ``offload_optimizer`` take the config's device
    string, e.g. ``"cpu"`` / ``"nvme"``): the optimizer's fp32 masters +
    moments (and the grads that feed them) follow ``offload_optimizer``,
    the param shards follow ``offload_param`` — those classes stop
    counting against ``hbm_bytes``.  Classes homed on ``"cpu"`` are
    instead priced against ``host_bytes`` when the caller provides it
    (the r04 ladder died in HOST resource exhaustion, not HBM); NVMe
    classes are treated as unbounded.

    ``chunk_bytes`` prices the chunked host-step pipeline
    (``offload_optimizer.working_set_bytes > 0``): grads stay
    device-homed (the grads program materializes them in HBM/host-placed
    shardings and only O(chunk) crosses at a time), the cpu tier adds a
    double-buffered working set (grad chunk + the (3,n) state rows, two
    buffers deep) to the host need, and the nvme tier's host need is
    ONLY that working set — the state itself lives in chunk files.

    ``comm_quant`` adds the error-feedback residual under a ``comm``
    class (see :func:`estimate_memory_breakdown`); it is always
    device-homed — offload never re-homes it — so quantized-DP configs
    near the fit boundary stop being under-priced."""
    bd = estimate_memory_breakdown(model_info, zero_stage, dp_size,
                                   micro_batch, seq_len, dtype,
                                   tp_size=tp_size, pp_size=pp_size,
                                   sp_size=sp_size, comm_quant=comm_quant,
                                   comm_group_size=comm_group_size)
    cal = float(calibration) if calibration else 1.0
    home = {k: "device" for k in bd if k != "total"}
    if offload_optimizer:
        home["optimizer"] = offload_optimizer
        home["grads"] = offload_optimizer
    if offload_param:
        home["params"] = offload_param
    chunk_working_set = 0
    if chunk_bytes and offload_optimizer in ("cpu", "nvme"):
        home["grads"] = "device"
        # per buffered chunk: 1 grad row + 3 state rows, double-buffered
        chunk_working_set = int(2 * 4 * chunk_bytes)
    device_classes = [k for k, h in home.items() if h == "device"]
    host_classes = [k for k, h in home.items() if h == "cpu"]
    predicted = int(sum(bd[k] for k in device_classes) * cal)
    host_need = int(sum(bd[k] for k in host_classes) * cal)
    # (nvme-homed state never entered host_classes, so the nvme tier's
    # host need is exactly this working set)
    host_need += chunk_working_set
    fit_device = predicted <= int(hbm_bytes)
    fit_host = host_bytes is None or host_need <= int(host_bytes)
    if not fit_device:
        dominant = max(device_classes, key=lambda k: bd[k])
        shortfall = predicted - int(hbm_bytes)
    elif not fit_host:
        dominant = (max(host_classes, key=lambda k: bd[k])
                    if host_classes else "optimizer")
        shortfall = host_need - int(host_bytes)
    else:
        dominant = max((k for k in bd if k != "total"),
                       key=lambda k: bd[k])
        shortfall = 0
    return {
        "predicted_peak_bytes": predicted,
        "predicted_fit": fit_device and fit_host,
        "hbm_bytes": int(hbm_bytes),
        "host_bytes": None if host_bytes is None else int(host_bytes),
        "host_resident_bytes": host_need,
        "chunk_working_set_bytes": chunk_working_set,
        "calibration": round(cal, 4),
        "breakdown": bd,
        "dominant_class": dominant,
        "shortfall_bytes": max(0, shortfall),
    }


def enumerate_meshes(n_devices: int, model_cfg) -> "List[Dict[str, int]]":
    """All valid mesh factorizations of ``n_devices`` over
    data×tensor×pipe×seq(×expert for MoE), pruned by model divisibility
    (heads % tp, kv_heads % tp, heads % sp, layers % pp, experts % ep) —
    the tp/pp/sp/ep sweep dimension of the reference autotuner's space.
    """
    heads = getattr(model_cfg, "num_heads", 1) or 1
    kv_heads = getattr(model_cfg, "num_kv_heads", None) or heads
    layers = getattr(model_cfg, "num_layers", 1) or 1
    experts = getattr(model_cfg, "num_experts", 0) or 0
    is_moe = experts > 1

    def divisors(n):
        return [d for d in range(1, n + 1) if n % d == 0]

    meshes = []
    for tp in divisors(n_devices):
        if heads % tp or kv_heads % tp:
            continue
        for pp in divisors(n_devices // tp):
            if layers % pp:
                continue
            for sp in divisors(n_devices // (tp * pp)):
                # only query heads constrain sp: the Ulysses layer expands
                # KV for GQA when kv_heads < sp (sequence/layer.py:43)
                if heads % sp:
                    continue
                if tp > 1 and sp > 1 and (pp > 1 or heads % (tp * sp)):
                    # tensor×seq composition shards heads jointly over
                    # both axes (sequence/layer.py) — needs tp·sp | heads,
                    # and adding pipe on top still trips the SPMD
                    # partitioner (XLA abort), so tp×sp×pp stays pruned.
                    # (tp×sp is validated on the XLA attention path; on a
                    # real TPU the Pallas kernel route is covered by the
                    # crash-isolated trial, which scores an abort as 0.)
                    continue
                rem = n_devices // (tp * pp * sp)
                for ep in (divisors(rem) if is_moe else [1]):
                    if is_moe and ep > 1 and experts % ep:
                        continue
                    mesh = {"data": rem // ep}
                    if tp > 1:
                        mesh["tensor"] = tp
                    if pp > 1:
                        mesh["pipe"] = pp
                    if sp > 1:
                        mesh["seq"] = sp
                    if ep > 1:
                        mesh["expert"] = ep
                    meshes.append(mesh)  # every (tp,pp,sp,ep) is distinct
    return meshes


def generate_tuning_space(model_info: ModelInfo, dp_size: int, seq_len: int,
                          hbm_bytes: int, dtype: str = "bf16",
                          stages=(0, 1, 2, 3),
                          max_micro_batch: int = 64,
                          meshes: Optional[List[Dict[str, int]]] = None,
                          calibration: float = 1.0
                          ) -> List[Dict[str, Any]]:
    """Candidate (mesh, zero_stage, micro_batch) configs that fit the
    memory budget (ref tuning-space templates + the mesh sweep).
    ``calibration`` scales the analytic estimate by the memory auditor's
    frozen ``model_drift`` ratio (:func:`load_memory_calibration`) so
    pruning tracks what XLA actually allocates on this backend."""
    space = []
    # mesh=None = "not sweeping": candidates carry no mesh key, so the
    # caller's base_config mesh passes through trials untouched
    for mesh in (meshes if meshes else [None]):
        if mesh is None:
            dp, tp, pp, sp = dp_size, 1, 1, 1
        else:
            dp = mesh.get("data", 1) * mesh.get("expert", 1)
            tp, pp, sp = (mesh.get("tensor", 1), mesh.get("pipe", 1),
                          mesh.get("seq", 1))
        if sp > 1 and seq_len % sp:
            continue
        for stage in stages:
            if pp > 1 and stage >= 2:
                continue  # engine: pipeline composes with ZeRO-0/1 specs
            mb = 1
            while mb <= max_micro_batch:
                need = int(estimate_memory_per_device(
                    model_info, stage, max(1, dp), mb, seq_len, dtype,
                    tp_size=tp, pp_size=pp, sp_size=sp)
                    * (float(calibration) or 1.0))
                if need <= hbm_bytes:
                    cand = {"zero_stage": stage, "micro_batch": mb,
                            "est_bytes": need}
                    if mesh is not None:
                        cand["mesh"] = mesh
                    space.append(cand)
                mb *= 2
    return space


@dataclass
class TrialResult:
    config: Dict[str, Any]
    throughput: float  # samples/sec
    step_seconds: float
    error: Optional[str] = None


class Autotuner:
    """Ref Autotuner (autotuning/autotuner.py:42).

    ``tune`` returns (best_ds_config, results).  ``mode``: "grid" tries the
    whole space; "random" samples ``max_trials``; "model_based" orders by
    estimated memory headroom (bigger batch first) and early-stops after
    ``patience`` non-improving trials; "planner" seeds the space with the
    plan compiler's ranked candidates (deepspeed_tpu.planner — static
    census-priced step-time model) instead of the blind pow2 ladder,
    falling back to model_based ordering if planning fails.
    """

    def __init__(self, model_cfg, base_config: Dict[str, Any],
                 seq_len: int = 64, mode: str = "model_based",
                 max_trials: int = 8, steps_per_trial: int = 3,
                 hbm_bytes: Optional[int] = None, seed: int = 0,
                 tune_mesh: bool = False, n_devices: Optional[int] = None,
                 isolate_trials: bool = True,
                 trial_timeout: Optional[float] = None,
                 calibration: Any = None):
        self.model_cfg = model_cfg
        self.base_config = base_config
        self.seq_len = seq_len
        self.mode = mode
        self.max_trials = max_trials
        self.steps_per_trial = steps_per_trial
        self.hbm_bytes = hbm_bytes or (16 << 30)
        self.seed = seed
        self.tune_mesh = tune_mesh
        self.n_devices = n_devices
        # subprocess isolation (ref: experiments run as separate jobs) —
        # an aborting/OOMing candidate must not kill the tuner itself
        self.isolate_trials = isolate_trials
        # generous default: engine build + XLA compile + timed steps
        self.trial_timeout = trial_timeout or (600.0 + 30.0 * steps_per_trial)
        # memory-model calibration attached to tuning-space pruning:
        # None = uncalibrated (1.0, historical behavior), "auto" = the
        # memory auditor's frozen model_drift ratio for this backend
        # (tools/memory_baseline.json), or an explicit float
        self._facts: Optional[Dict[str, Any]] = None
        if calibration == "auto":
            calibration = load_memory_calibration(
                backend=self._device_facts()["platform"])
        self.calibration = float(calibration) if calibration else 1.0
        self.results: List[TrialResult] = []

    def _device_facts(self) -> Dict[str, Any]:
        """Platform and device count of the backend the trials run on.
        With isolated trials this process must never initialise a JAX
        backend — it would hold the chip its trial children need — so a
        child is asked instead."""
        if self._facts is None:
            if self.isolate_trials:
                from deepspeed_tpu.utils.platform import \
                    device_facts_from_child

                self._facts = device_facts_from_child()
            else:
                import jax

                self._facts = {"platform": jax.default_backend(),
                               "n_devices": len(jax.devices())}
        return self._facts

    # ------------------------------------------------------------------
    def model_info(self) -> ModelInfo:
        from deepspeed_tpu.profiling import get_model_profile

        prof = get_model_profile(self.model_cfg, 1, self.seq_len)
        return ModelInfo(num_params=prof["params"],
                         hidden_size=self.model_cfg.hidden_size,
                         num_layers=self.model_cfg.num_layers,
                         vocab_size=self.model_cfg.vocab_size)

    def _space(self) -> List[Dict[str, Any]]:
        if self.mode == "planner":
            # plan-compiler seeding: ranked candidates from the static
            # planner (census-priced step-time model) replace the blind
            # pow2 enumeration — trials then confirm the analytic ranking
            try:
                from deepspeed_tpu.planner import seed_candidates

                n = self.n_devices or self._device_facts()["n_devices"]
                cands = seed_candidates(
                    self.model_cfg, seq_len=self.seq_len, chips=n,
                    hbm_bytes=self.hbm_bytes,
                    calibration=self.calibration, top=self.max_trials)
                if cands:
                    return cands
            except Exception as e:  # planner unavailable → pow2 fallback
                logger.warning(f"planner seeding failed ({e}); "
                               "falling back to model_based space")
        mesh = self.base_config.get("mesh") or {}
        dp = int(mesh.get("data", 1)) * int(mesh.get("expert", 1))
        meshes = None
        if self.tune_mesh:
            n = self.n_devices or self._device_facts()["n_devices"]
            meshes = enumerate_meshes(n, self.model_cfg)
        space = generate_tuning_space(self.model_info(), max(1, dp),
                                      self.seq_len, self.hbm_bytes,
                                      meshes=meshes,
                                      calibration=self.calibration)
        if self.mode == "random":
            rng = np.random.default_rng(self.seed)
            rng.shuffle(space)
            return space[:self.max_trials]
        if self.mode in ("model_based", "planner"):
            space.sort(key=lambda c: (-c["micro_batch"], -c["zero_stage"]))
            return space[:self.max_trials]
        return space  # grid

    def _trial_config(self, cand: Dict[str, Any]) -> Dict[str, Any]:
        cfg = copy.deepcopy(self.base_config)
        cfg["train_micro_batch_size_per_gpu"] = cand["micro_batch"]
        cfg.setdefault("gradient_accumulation_steps", 1)
        cfg.pop("train_batch_size", None)
        cfg.setdefault("zero_optimization", {})["stage"] = cand["zero_stage"]
        if cand.get("mesh"):
            cfg["mesh"] = dict(cand["mesh"])
        # planner-seeded candidates carry whole config blocks
        # (comm_quantization / step_schedule / offload) as overrides
        for k, v in (cand.get("overrides") or {}).items():
            cfg[k] = copy.deepcopy(v)
        return cfg

    def run_trial(self, cand: Dict[str, Any]) -> TrialResult:
        if self.isolate_trials:
            return self._run_trial_subprocess(cand)
        return self._run_trial_inprocess(cand)

    def _run_trial_subprocess(self, cand: Dict[str, Any]) -> TrialResult:
        """Run one trial in a fresh subprocess (the reference launches whole
        experiment jobs, autotuner.py:404): an OOM, compile failure, or a
        hard XLA abort kills only the trial, never the tuner.  The trial
        body is deepspeed_tpu.autotuning.trial_runner (shared with the
        in-process path)."""
        import json
        import pickle
        import re as _re
        import subprocess
        import sys
        import tempfile

        from deepspeed_tpu.autotuning.trial_runner import RESULT_PREFIX

        payload = {"model_cfg": self.model_cfg,
                   "config": self._trial_config(cand),
                   "seq_len": self.seq_len,
                   "steps": self.steps_per_trial}
        with tempfile.NamedTemporaryFile(suffix=".pkl", delete=False) as f:
            pickle.dump(payload, f)
            path = f.name
        import deepspeed_tpu

        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(deepspeed_tpu.__file__)))
        # the child inherits the platform through the environment
        # ($JAX_PLATFORMS); on the virtual CPU mesh it is also given the
        # device count this tuner was told to tune for
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        if self.n_devices and env.get("JAX_PLATFORMS") == "cpu":
            flags = _re.sub(r"--xla_force_host_platform_device_count=\d+",
                            "", env.get("XLA_FLAGS", ""))
            env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_"
                                f"count={self.n_devices}").strip()
        try:
            out = subprocess.run(
                [sys.executable, "-m",
                 "deepspeed_tpu.autotuning.trial_runner", path],
                capture_output=True, timeout=self.trial_timeout, env=env)
            for line in out.stdout.decode(errors="replace").splitlines():
                if line.startswith(RESULT_PREFIX):
                    r = json.loads(line[len(RESULT_PREFIX):])
                    return TrialResult(cand, throughput=r["throughput"],
                                       step_seconds=r["step_seconds"])
            err = out.stderr.decode(errors="replace")[-300:]
            logger.warning(f"autotuner trial {cand} failed (rc={out.returncode})")
            return TrialResult(cand, throughput=0.0,
                               step_seconds=float("inf"), error=err)
        except subprocess.TimeoutExpired:
            logger.warning(f"autotuner trial {cand} timed out after "
                           f"{self.trial_timeout:.0f}s")
            return TrialResult(cand, throughput=0.0,
                               step_seconds=float("inf"), error="timeout")
        finally:
            os.unlink(path)

    def _run_trial_inprocess(self, cand: Dict[str, Any]) -> TrialResult:
        from deepspeed_tpu.autotuning.trial_runner import run_timed_trial
        from deepspeed_tpu.parallel import topology

        cfg = self._trial_config(cand)
        try:
            r = run_timed_trial(self.model_cfg, cfg, self.seq_len,
                                self.steps_per_trial)
            return TrialResult(cand, throughput=r["throughput"],
                               step_seconds=r["step_seconds"])
        except Exception as e:  # OOM / compile failure → score 0
            logger.warning(f"autotuner trial {cand} failed: {e}")
            return TrialResult(cand, throughput=0.0, step_seconds=float("inf"),
                               error=str(e))
        finally:
            topology._GLOBAL_TOPOLOGY = None

    def tune(self, patience: int = 3):
        """→ (best_config_dict, [TrialResult...])."""
        best: Optional[TrialResult] = None
        stale = 0
        for cand in self._space():
            res = self.run_trial(cand)
            self.results.append(res)
            logger.info(f"autotuner: {cand} → "
                        f"{res.throughput:.2f} samples/s")
            if best is None or res.throughput > best.throughput:
                best, stale = res, 0
            else:
                stale += 1
                if self.mode == "model_based" and stale >= patience:
                    break
        if best is None or best.throughput <= 0:
            raise RuntimeError("autotuning found no runnable config")
        return self._trial_config(best.config), self.results
