#!/usr/bin/env python
"""Telemetry docs/schema lint (runs in the tier-1 suite via
tests/test_telemetry.py, and standalone: ``python tools/telemetry_check.py``).

Checks:
1. every MonitorMaster tag the telemetry bridge or the serving metrics
   can emit appears in docs/OBSERVABILITY.md;
2. every Prometheus metric name the train/serving registries create
   appears in the docs;
3. the StepRecord JSONL schema is stable: ``schema: 1``, keys sorted in
   the serialized line, and the top-level key set matches the frozen
   list below (update EXPECTED_RECORD_KEYS *and the docs table* in the
   same commit as any schema change);
4. the tracing vocabulary is stable and documented: span / instant-event
   names (telemetry/tracing.py) and flight-recorder bundle reasons
   (telemetry/flight.py) match the frozen lists below AND appear in the
   docs span table;
5. an exported trace is well-formed Chrome trace-event JSON — a sample
   trace covering every span/event name is generated and validated
   (``validate_chrome_trace`` is also importable for ad-hoc files);
6. the device-stage vocabulary (telemetry/tracing.py STAGE_NAMES) matches
   the frozen list below, every name is in the docs stage table, every
   name is used by a ``jax.named_scope`` in the package, and every
   ``named_scope`` in the package uses names of the table and no other.
"""

from __future__ import annotations

import json
import os
import re
import sys
import tempfile
from typing import Any, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the shared frozen-vocabulary engine (deepspeed_tpu/analysis/vocab.py):
# every "frozen list == module list, names documented" contract below
# is ONE VocabSpec registration, shared with tools/graft_lint.py
from deepspeed_tpu.analysis.vocab import VocabSpec  # noqa: E402
from deepspeed_tpu.analysis.vocab import check_all as _vocab_check  # noqa: E402

DOCS = os.path.join(REPO, "docs", "OBSERVABILITY.md")

# frozen with schema version 3 (v2 added offload_overlap_fraction for
# the chunked host-optimizer pipeline; v3 added run_id) —
# telemetry_check is the tripwire
EXPECTED_SCHEMA_VERSION = 3
EXPECTED_RECORD_KEYS = [
    "achieved_flops_per_sec", "comm", "flops_per_step", "flops_source",
    "goodput", "grad_norm", "hbm", "kind", "loss", "loss_scale", "lr",
    "mfu", "offload_overlap_fraction", "peak_flops_per_sec", "run_id",
    "schema", "serving", "skipped", "step", "tokens", "tokens_per_sec",
    "wall_time_s",
]

# frozen tracing vocabulary (telemetry/tracing.py SPAN_NAMES/EVENT_NAMES
# and telemetry/flight.py FLIGHT_REASONS must match, and every name must
# appear in the docs span table — same contract as the record keys)
EXPECTED_SPAN_NAMES = [
    "fleet.sample",
    "offload.d2h", "offload.h2d", "offload.host_step",
    "recovery.outage", "router.leg", "router.request",
    "serve.admission_block", "serve.admit_pass", "serve.decode",
    "serve.deliver", "serve.handoff", "serve.idle_wait",
    "serve.prefill", "serve.queue_wait", "serve.request", "serve.step",
    "spec.draft", "spec.verify",
    "train.data_ingest", "train.dispatch", "train.step", "train.sync",
    "train.telemetry",
    "v2.dispatch", "v2.fetch", "v2.h2d", "v2.ragged_step", "v2.schedule",
    "v2.state_alloc",
]
EXPECTED_EVENT_NAMES = [
    "chaos.inject", "fleet.brownout", "fleet.heal",
    "recovery.detected", "recovery.replan", "recovery.restart",
    "recovery.resumed", "router.dispatch", "router.failover", "serve.emit",
    "serve.enqueue", "serve.finish", "serve.first_token", "serve.preempt",
    "serve.prefix_hit", "slo.violation", "spec.accept", "watchdog.fire",
]
# device stages: the ``jax.named_scope`` names of the jitted serving steps
EXPECTED_STAGE_NAMES = [
    "attn.append", "attn.out", "attn.qkv", "attn.read", "embed", "head",
    "latent.down", "latent.gather", "latent.index", "latent.read",
    "latent.select", "latent.window", "layers", "mlp",
    "moe.combine", "moe.dispatch", "moe.experts", "moe.router", "moe.shared",
    "mtp", "ssm.conv", "ssm.in", "ssm.out", "ssm.scan", "verify",
]
PACKAGE = os.path.join(REPO, "deepspeed_tpu")
_NAMED_SCOPE = re.compile(r"named_scope\(((?:[^()]|\([^()]*\))*)\)")
_LITERAL = re.compile(r"""["']([^"']+)["']""")
EXPECTED_FLIGHT_REASONS = ["watchdog", "serve_crash", "engine_crash",
                           "manual", "recovery", "fleet"]

# frozen quantized-collective comm-op vocabulary (comm/quantized.py
# QUANT_COMM_OPS): every wire movement of the quantized ZeRO collectives
# is recorded in CommsLogger — and therefore surfaces in the StepRecord
# `comm` field — under one of these names.  Each must be documented in
# docs/QUANTIZED_COMM.md.
QUANT_DOCS = os.path.join(REPO, "docs", "QUANTIZED_COMM.md")
EXPECTED_QUANT_COMM_OPS = ["quant_all_gather", "quant_reduce_scatter"]

# frozen overlap-scheduler vocabulary (autotuning/overlap_scheduler.py;
# docs/AUTOTUNING.md): decision names and evidence keys must match the
# module AND be documented; the step_schedule config keys must be
# documented; and the capture-report keys the scheduler consumes
# (telemetry/capture.py) must be documented too.
AUTOTUNING_DOCS = os.path.join(REPO, "docs", "AUTOTUNING.md")
EXPECTED_SCHEDULE_DECISIONS = ["decomposed_update", "fused_gather_matmul",
                               "noop", "ring_interleave", "zero3_prefetch"]
EXPECTED_EVIDENCE_KEYS = ["dominant_collective", "exposed_comm_ms",
                          "overlap_fraction", "overlap_source",
                          "probe_step", "static_census", "static_memory"]
EXPECTED_STEP_SCHEDULE_KEYS = [
    "decisions", "fused_gather_matmul", "fused_reduce_scatter",
    "gather_prefetch_depth", "mode", "overlap_threshold",
    "param_persistence_threshold", "prefetch_bucket_size", "probe_steps",
    "ring_interleave", "weight_update",
]
CAPTURE_REPORT_SCHED_KEYS = ["dominant_collective", "exposed_ms",
                             "overlap_estimate", "spans", "step"]

# frozen serving vocabulary (docs/SERVING.md): every router-tier
# Prometheus metric (RouterMetrics over a fresh registry; per-replica
# counters normalized to their documented `router_routed_r*_total`
# wildcard) must appear in the doc, and the replica tier names
# (serving/disagg.py) must match their module and be documented.
SERVING_DOCS = os.path.join(REPO, "docs", "SERVING.md")
EXPECTED_REPLICA_TIERS = ["prefill", "decode", "unified"]

# frozen static-graph-audit vocabulary (deepspeed_tpu/analysis/report.py;
# docs/STATIC_ANALYSIS.md): finding kinds, severities, and the audit
# report's frozen key sets — same tripwire contract as the StepRecord
# schema, linted through the shared VocabSpec engine.
STATIC_DOCS = os.path.join(REPO, "docs", "STATIC_ANALYSIS.md")
EXPECTED_FINDING_KINDS = [
    "collective_mismatch", "donation_miss", "dtype_promotion",
    "host_callback", "implicit_resharding", "model_drift",
    "peak_regression", "recompile_hazard", "remat_miss",
    "seam_violation", "unsharded_transient", "wire_dtype_mismatch",
]
EXPECTED_AUDIT_SEVERITIES = ["info", "warning", "high"]
EXPECTED_AUDIT_REPORT_KEYS = ["backend", "census", "donation", "findings",
                              "label", "num_partitions", "schema"]
EXPECTED_AUDIT_CENSUS_KEYS = ["count", "dtype", "group_size", "kind",
                              "payload_bytes", "wire_bytes"]
EXPECTED_AUDIT_FINDING_KEYS = ["detail", "fingerprint", "kind", "message",
                               "severity", "where"]
EXPECTED_AUDIT_DONATION_KEYS = ["aliased", "declared", "missed",
                                "missed_bytes"]

# frozen memory-plan-audit vocabulary (analysis/report.py MemoryAuditReport;
# docs/STATIC_ANALYSIS.md): report/totals/buffer/budget/calibration key
# sets and the buffer-classification classes — same tripwire contract
# as the graph audit schema.
EXPECTED_MEMORY_REPORT_KEYS = ["backend", "budget", "buffers",
                               "calibration", "class_bytes", "findings",
                               "label", "num_partitions", "schema",
                               "totals"]
EXPECTED_MEMORY_TOTALS_KEYS = ["alias_bytes", "argument_bytes",
                               "generated_code_bytes", "output_bytes",
                               "peak_bytes", "temp_bytes"]
EXPECTED_BUFFER_KEYS = ["bytes", "category", "dtype", "op", "shape"]
EXPECTED_MEMORY_CLASSES = ["activations", "grads", "opt_state", "other",
                           "params", "transients"]
EXPECTED_BUDGET_KEYS = ["bucketed_peak_bytes", "budget_bytes",
                        "peak_bytes"]
EXPECTED_CALIBRATION_KEYS = ["analytic_bytes", "measured_bytes", "ratio"]

# frozen host-tiered offload vocabulary (runtime/offload.py
# ChunkedHostOptimizer + nvme/chunk_store.py; docs/OFFLOAD.md): the
# chunked config knobs must be real OffloadOptimizerConfig fields
# documented in the offload doc — same tripwire contract as every other
# vocabulary.
OFFLOAD_DOCS = os.path.join(REPO, "docs", "OFFLOAD.md")
OFFLOAD_CONFIG_KEYS = ["buffer_count", "chunk_bytes", "nvme_path",
                       "working_set_bytes"]

# frozen recovery vocabulary (resilience/supervisor.py RECOVERY_STATES;
# docs/ELASTICITY.md): the supervisor's state machine follows the same
# contract as every other vocabulary — frozen list matches the module,
# every name documented.
ELASTICITY_DOCS = os.path.join(REPO, "docs", "ELASTICITY.md")
EXPECTED_RECOVERY_STATES = ["running", "detected", "dumped", "stopped",
                            "replanned", "restarted", "resumed", "failed"]

# frozen plan-compiler vocabulary (deepspeed_tpu/planner; docs/PLANNER.md):
# the per-candidate evidence keys the planner pins, the link classes its
# cost model prices and the offload tier ladder it enumerates all
# follow the standard contract — frozen list matches the module, every
# name documented.
PLANNER_DOCS = os.path.join(REPO, "docs", "PLANNER.md")
EXPECTED_PLAN_EVIDENCE_KEYS = [
    "census", "census_mode", "dominant_class", "dominant_cost_term",
    "overlap_fraction", "predicted_peak_bytes", "predicted_step_ms",
    "wire_bytes_total",
]
EXPECTED_LINK_CLASSES = ["ici", "dcn", "pcie", "nvme"]
EXPECTED_OFFLOAD_TIER_NAMES = ["none", "opt_cpu", "cpu", "cpu_chunked",
                               "nvme_chunked", "nvme"]

# frozen fleet-observability vocabulary (serving/fleet.py TierSnapshot,
# telemetry/slo.py SLO ledger, serving/disagg.py request timelines;
# docs/OBSERVABILITY.md "Fleet snapshots & SLO ledger"): snapshot keys,
# SLO block/scenario/ledger/target keys, and stitched-timeline keys each
# follow the standard contract — frozen list matches the module, every
# key documented.
# Per-tier Prometheus gauges are documented via their `fleet_*_<key>`
# wildcard rows (tiers substitute into the `*`).
EXPECTED_TIER_SNAPSHOT_SCHEMA = 2      # v2 added run_id
EXPECTED_TIER_SNAPSHOT_KEYS = [
    "evictable_headroom_blocks", "handoff_bytes_per_sec",
    "handoffs_per_sec", "kv_utilization", "prefix_hit_rate",
    "queue_depth", "queue_wait_p50_ms", "queue_wait_p95_ms",
    "queue_wait_p99_ms", "replicas_alive", "run_id", "running", "schema",
    "slo_violation", "spec_accept_rate", "tick", "tier",
    "tokens_per_sec", "tpot_p50_ms", "tpot_p95_ms", "tpot_p99_ms", "ts",
    "ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
]
EXPECTED_SLO_TARGET_KEYS = ["queue_wait_p95_ms", "tpot_p95_ms",
                            "ttft_p95_ms"]
EXPECTED_SLO_BLOCK_KEYS = ["attainment", "by_scenario",
                           "error_budget_burn", "objective", "targets",
                           "violations"]
EXPECTED_SLO_SCENARIO_KEYS = ["attainment", "n", "tpot_attainment",
                              "ttft_attainment", "violations"]
EXPECTED_SLO_LEDGER_KEYS = ["attainment", "error_budget_burn", "ticks",
                            "violations"]
EXPECTED_TIMELINE_KEYS = ["decode_ms", "failovers", "handoff_bytes",
                          "handoff_ms", "prefill_ms", "total_ms",
                          "trace_id", "uid"]

# frozen chaos / self-healing vocabulary (resilience/chaos.py fault
# kinds + injection points, serving/supervisor.py health states,
# serving/admission.py brownout ladder; docs/SERVING.md "Fault injection
# & self-healing"): each frozen list matches its module and every name
# is documented — the standard vocabulary contract.
EXPECTED_FAULT_KINDS = ["admission_storm", "cancel_storm", "handoff_fail",
                        "replica_crash", "replica_hang", "slow_replica"]
EXPECTED_INJECTION_POINTS = ["engine.step", "router.dispatch",
                             "server.handoff", "server.step", "train.step"]
EXPECTED_HEALTH_STATES = ["healthy", "suspect", "stuck", "straggler",
                          "dead", "quarantined", "respawned", "retired"]
EXPECTED_BROWNOUT_LEVELS = ["normal", "shed_speculation", "cap_decode",
                            "shed_low_priority", "reject_new"]


def _exported_monitor_tags() -> List[str]:
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.telemetry import EXPORT_TAGS

    serving_tags = [tag for tag, _, _ in ServingMetrics().events(0)]
    return sorted(set(EXPORT_TAGS) | set(serving_tags))


def _registry_metric_names() -> List[str]:
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.telemetry import Telemetry

    tel = Telemetry(TelemetryConfig(enabled=True))
    ServingMetrics(registry=tel.registry)
    return [m.name for m in tel.registry.collect()]


def check_tags_documented(docs_path: str = DOCS) -> List[str]:
    """Every exported tag / metric name must appear in the docs tables.
    Suffix-flattened serving distribution tags (serving/ttft_p50 …) are
    accepted via their documented `serving/ttft_*` wildcard row."""
    errors = []
    try:
        with open(docs_path, "r", encoding="utf-8") as f:
            docs = f.read()
    except OSError as e:
        return [f"cannot read {docs_path}: {e}"]
    for tag in _exported_monitor_tags():
        base = tag.rsplit("_", 1)[0]
        if tag not in docs and f"{base}_*" not in docs:
            errors.append(f"monitor tag {tag!r} not documented in "
                          f"{os.path.basename(docs_path)}")
    for name in _registry_metric_names():
        if name not in docs:
            errors.append(f"prometheus metric {name!r} not documented")
    return errors


def check_schema() -> List[str]:
    """JSONL schema stability: versioned, sorted, frozen key set."""
    from deepspeed_tpu.telemetry import StepRecord, record_keys

    errors = []
    rec = StepRecord(step=1, wall_time_s=0.5, tokens=100,
                     flops_per_step=1e9, peak_flops_per_sec=1e12)
    d = json.loads(rec.to_json())
    if d.get("schema") != EXPECTED_SCHEMA_VERSION:
        errors.append(f"schema field is {d.get('schema')!r}, expected "
                      f"{EXPECTED_SCHEMA_VERSION}")
    keys = list(d.keys())
    if keys != sorted(keys):
        errors.append("JSONL keys are not sorted in serialized output")
    if sorted(keys) != EXPECTED_RECORD_KEYS:
        errors.append(
            "StepRecord key set drifted from the frozen schema: "
            f"extra={sorted(set(keys) - set(EXPECTED_RECORD_KEYS))}, "
            f"missing={sorted(set(EXPECTED_RECORD_KEYS) - set(keys))} — "
            "bump SCHEMA_VERSION and update EXPECTED_RECORD_KEYS + docs")
    if record_keys() != EXPECTED_RECORD_KEYS:
        errors.append("telemetry.record.record_keys() disagrees with the "
                      "frozen key list")
    # mfu/goodput invariants the docs promise
    if not (0.0 < d["mfu"] <= 1.0):
        errors.append(f"sample record mfu {d['mfu']} outside (0, 1]")
    return errors


# arguments the ``v2.schedule`` span carries for a latent-attention model
# (inference/v2/latent.py latent_step_counts): the benchmark's readers
# read them by name
EXPECTED_LATENT_SCHEDULE_ARGS = ["expert_rows", "index_pairs", "latent_rows",
                                 "selected_keys", "walked_pairs",
                                 "window_keys"]


# arguments the ``v2.schedule`` span carries for a model that mixes window
# and full attention by layer (engine_v2.window_step_counts), and those of
# its pools' ``v2.state_alloc``: the benchmark's readers read them by name
EXPECTED_WINDOW_SCHEDULE_ARGS = ["expert_rows", "full_kv_rows", "full_pages",
                                 "full_qk_pairs", "pages_freed",
                                 "window_kv_rows", "window_pages",
                                 "window_qk_pairs"]
# (the last eight from engine_v2.mixed_alloc_counts: what the two pools
# keep a kind of layer, and the Pallas calls ONE step program makes)
EXPECTED_WINDOW_ALLOC_ARGS = ["full_pool_bytes", "window_layers",
                              "window_pool_bytes", "full_page_bytes",
                              "window_page_bytes", "full_kv_heads",
                              "window_kv_heads", "key_width", "value_width",
                              "sink_layers", "kernel_calls_per_step"]


# arguments a run's first ``train.step`` span carries where the model
# runs the repo flash kernels (runtime/engine.py _flash_executed_shares)
EXPECTED_TRAIN_STEP_ARGS = ["flash_executed_share_bwd",
                            "flash_executed_share_fwd"]


# arguments the ``v2.schedule`` span carries for a one-mixer-a-layer model
# beside a mixer's (engine_v2.hybrid_step_counts), and those it adds to
# ``v2.state_alloc`` (engine_v2.hybrid_alloc_counts): the benchmark's
# readers read them by name
EXPECTED_HYBRID_SCHEDULE_ARGS = ["expert_rows", "kv_pages_held"]
EXPECTED_HYBRID_ALLOC_ARGS = ["attn_layers", "expert_layers",
                              "kernel_calls_per_step", "kv_pool_bytes",
                              "page_bytes", "slot_bytes", "ssm_layers",
                              "state_pool_bytes"]


def check_span_names() -> List[str]:
    """Tracing vocabulary: frozen lists match the modules, every name is
    in the docs span table."""
    from deepspeed_tpu.telemetry.flight import FLIGHT_REASONS
    from deepspeed_tpu.telemetry.tracing import EVENT_NAMES, SPAN_NAMES

    errors = []
    if sorted(SPAN_NAMES) != sorted(EXPECTED_SPAN_NAMES):
        errors.append(
            "tracing.SPAN_NAMES drifted from the frozen list: "
            f"extra={sorted(set(SPAN_NAMES) - set(EXPECTED_SPAN_NAMES))}, "
            f"missing={sorted(set(EXPECTED_SPAN_NAMES) - set(SPAN_NAMES))}"
            " — update EXPECTED_SPAN_NAMES + the docs span table together")
    if sorted(EVENT_NAMES) != sorted(EXPECTED_EVENT_NAMES):
        errors.append(
            "tracing.EVENT_NAMES drifted from the frozen list: "
            f"extra={sorted(set(EVENT_NAMES) - set(EXPECTED_EVENT_NAMES))},"
            f" missing="
            f"{sorted(set(EXPECTED_EVENT_NAMES) - set(EVENT_NAMES))}")
    if sorted(FLIGHT_REASONS) != sorted(EXPECTED_FLIGHT_REASONS):
        errors.append("flight.FLIGHT_REASONS drifted from the frozen list")
    try:
        with open(DOCS, "r", encoding="utf-8") as f:
            docs = f.read()
    except OSError as e:
        return errors + [f"cannot read {DOCS}: {e}"]
    for name in list(SPAN_NAMES) + list(EVENT_NAMES):
        if f"`{name}`" not in docs:
            errors.append(f"span/event {name!r} not documented in "
                          f"{os.path.basename(DOCS)}")
    for reason in FLIGHT_REASONS:
        if f"`{reason}`" not in docs:
            errors.append(f"flight reason {reason!r} not documented")
    from deepspeed_tpu.inference.v2.latent import latent_step_counts
    from deepspeed_tpu.models import get_model_config

    counted = sorted(latent_step_counts(
        [(0, 1)], get_model_config("dots3-note-tiny")))
    if counted != EXPECTED_LATENT_SCHEDULE_ARGS:
        errors.append("latent.latent_step_counts drifted from the frozen "
                      f"v2.schedule arguments: {counted}")
    from deepspeed_tpu.inference.v2.engine_v2 import window_step_counts

    counted = sorted(window_step_counts(
        [(0, 1)], get_model_config("trinity-tiny"), (1, 1), 0))
    if counted != EXPECTED_WINDOW_SCHEDULE_ARGS:
        errors.append("engine_v2.window_step_counts drifted from the frozen "
                      f"v2.schedule arguments: {counted}")
    with open(os.path.join(PACKAGE, "inference", "v2", "engine_v2.py"),
              encoding="utf-8") as f:
        engine = f.read()
    for name in EXPECTED_WINDOW_ALLOC_ARGS:
        if f'"{name}":' not in engine:
            errors.append(f"v2.state_alloc argument {name!r} is documented "
                          "and the engine does not set it")
    with open(os.path.join(PACKAGE, "runtime", "engine.py"),
              encoding="utf-8") as f:
        engine = f.read()
    for name in EXPECTED_TRAIN_STEP_ARGS:
        if f'"{name}":' not in engine:
            errors.append(f"train.step argument {name!r} is documented and "
                          "the engine does not set it")
    for name in (EXPECTED_LATENT_SCHEDULE_ARGS + EXPECTED_WINDOW_SCHEDULE_ARGS
                 + EXPECTED_WINDOW_ALLOC_ARGS + EXPECTED_TRAIN_STEP_ARGS):
        if f"`{name}`" not in docs:
            errors.append(f"span argument {name!r} not documented "
                          f"in {os.path.basename(DOCS)}")
    return errors


def named_scopes(package: str = PACKAGE) -> List[tuple]:
    """``(file, line, [names])`` of every ``named_scope(...)`` call in the
    package's source: the string literals of its argument (a call may
    choose between two names)."""
    found = []
    for root, _, files in os.walk(package):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            for m in _NAMED_SCOPE.finditer(src):
                found.append((os.path.relpath(path, REPO),
                              src.count("\n", 0, m.start()) + 1,
                              _LITERAL.findall(m.group(1))))
    return found


def check_stage_names(package: str = PACKAGE) -> List[str]:
    """Device-stage vocabulary: the frozen list matches the module, every
    name is documented and used, and no scope uses a name outside it."""
    from deepspeed_tpu.telemetry.tracing import STAGE_NAMES

    errors = []
    if sorted(STAGE_NAMES) != sorted(EXPECTED_STAGE_NAMES):
        errors.append(
            "tracing.STAGE_NAMES drifted from the frozen list: "
            f"extra={sorted(set(STAGE_NAMES) - set(EXPECTED_STAGE_NAMES))}, "
            f"missing={sorted(set(EXPECTED_STAGE_NAMES) - set(STAGE_NAMES))}"
            " — update EXPECTED_STAGE_NAMES + the docs stage table together")
    try:
        with open(DOCS, "r", encoding="utf-8") as f:
            docs = f.read()
    except OSError as e:
        return errors + [f"cannot read {DOCS}: {e}"]
    used = set()
    for path, line, names in named_scopes(package):
        if not names:
            errors.append(f"{path}:{line}: named_scope without a literal "
                          "name: a stage is a name of STAGE_NAMES, written "
                          "where the scope is")
        for name in names:
            used.add(name)
            if name not in STAGE_NAMES:
                errors.append(f"{path}:{line}: named_scope({name!r}) is not "
                              "in tracing.STAGE_NAMES")
    for name in STAGE_NAMES:
        if f"`{name}`" not in docs:
            errors.append(f"stage {name!r} not documented in "
                          f"{os.path.basename(DOCS)}")
        if name not in used:
            errors.append(f"stage {name!r} is used by no named_scope in "
                          "the package")
    return errors


def _cross_link(docs_path: str, needle: str, what: str) -> List[str]:
    """A docs file must reference another doc (cross-link contract)."""
    try:
        with open(docs_path, "r", encoding="utf-8") as f:
            if needle not in f.read():
                return [f"{os.path.basename(docs_path)} does not "
                        f"cross-link {needle} from its {what} section"]
    except OSError as e:
        return [f"cannot read {docs_path}: {e}"]
    return []


def check_quant_comm() -> List[str]:
    """Quantized-collective telemetry: frozen comm-op vocabulary matches
    the module and every op is documented."""
    def _ops():
        from deepspeed_tpu.comm.quantized import QUANT_COMM_OPS

        return QUANT_COMM_OPS

    return _vocab_check([
        VocabSpec(name="quantized.QUANT_COMM_OPS",
                  expected=EXPECTED_QUANT_COMM_OPS, actual=_ops,
                  docs_path=QUANT_DOCS),
    ]) + _cross_link(DOCS, "QUANTIZED_COMM.md", "comm")


def check_router_serving() -> List[str]:
    """Router-tier vocabulary: every RouterMetrics Prometheus name is
    documented in docs/SERVING.md (per-replica counters via their
    ``_r*_`` wildcard), and the replica tier names match their module
    and are documented."""
    import re

    from deepspeed_tpu.serving.metrics import RouterMetrics

    names = [m.name for m in
             RouterMetrics(n_replicas=2).registry.collect()]

    def _tiers():
        from deepspeed_tpu.serving.disagg import REPLICA_TIERS

        return REPLICA_TIERS

    return _vocab_check([
        # registry-derived, so no frozen list — the docs contract only
        VocabSpec(name="router metrics", doc_names=names,
                  docs_path=SERVING_DOCS,
                  doc_normalize=lambda n: re.sub(r"_r\d+_", "_r*_", n)),
        VocabSpec(name="disagg.REPLICA_TIERS",
                  expected=EXPECTED_REPLICA_TIERS, actual=_tiers,
                  docs_path=SERVING_DOCS),
    ])


def check_autotuning() -> List[str]:
    """Overlap-scheduler vocabulary: frozen decision/evidence/config key
    lists match the modules and every name is documented in
    docs/AUTOTUNING.md."""
    from dataclasses import fields as dc_fields

    def _decisions():
        from deepspeed_tpu.autotuning.overlap_scheduler import \
            SCHEDULE_DECISIONS

        return SCHEDULE_DECISIONS

    def _evidence():
        from deepspeed_tpu.autotuning.overlap_scheduler import EVIDENCE_KEYS

        return EVIDENCE_KEYS

    def _ss_keys():
        from deepspeed_tpu.runtime.config import StepScheduleConfig

        return sorted(f.name for f in dc_fields(StepScheduleConfig))

    return _vocab_check([
        VocabSpec(name="overlap_scheduler.SCHEDULE_DECISIONS",
                  expected=EXPECTED_SCHEDULE_DECISIONS, actual=_decisions,
                  docs_path=AUTOTUNING_DOCS),
        VocabSpec(name="overlap_scheduler.EVIDENCE_KEYS",
                  expected=EXPECTED_EVIDENCE_KEYS, actual=_evidence,
                  docs_path=AUTOTUNING_DOCS),
        VocabSpec(name="StepScheduleConfig keys",
                  expected=EXPECTED_STEP_SCHEDULE_KEYS, actual=_ss_keys,
                  docs_path=AUTOTUNING_DOCS),
        VocabSpec(name="capture report scheduler keys",
                  expected=CAPTURE_REPORT_SCHED_KEYS,
                  docs_path=AUTOTUNING_DOCS),
    ]) + _cross_link(DOCS, "AUTOTUNING.md", "capture")


def check_graph_audit() -> List[str]:
    """Static-graph-audit vocabulary: finding kinds / severities / report
    key sets match deepspeed_tpu/analysis/report.py, every name is
    documented in docs/STATIC_ANALYSIS.md, and the autotuning docs
    cross-link the census-in-evidence field."""
    from deepspeed_tpu.analysis import (AUDIT_REPORT_KEYS, CENSUS_KEYS,
                                        DONATION_KEYS, FINDING_KEYS,
                                        FINDING_KINDS, SEVERITIES)

    return _vocab_check([
        VocabSpec(name="analysis.FINDING_KINDS",
                  expected=EXPECTED_FINDING_KINDS,
                  actual=lambda: FINDING_KINDS, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.SEVERITIES",
                  expected=EXPECTED_AUDIT_SEVERITIES,
                  actual=lambda: SEVERITIES, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.AUDIT_REPORT_KEYS",
                  expected=EXPECTED_AUDIT_REPORT_KEYS,
                  actual=lambda: AUDIT_REPORT_KEYS, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.CENSUS_KEYS",
                  expected=EXPECTED_AUDIT_CENSUS_KEYS,
                  actual=lambda: CENSUS_KEYS, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.FINDING_KEYS",
                  expected=EXPECTED_AUDIT_FINDING_KEYS,
                  actual=lambda: FINDING_KEYS, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.DONATION_KEYS",
                  expected=EXPECTED_AUDIT_DONATION_KEYS,
                  actual=lambda: DONATION_KEYS, docs_path=STATIC_DOCS),
    ]) + _cross_link(AUTOTUNING_DOCS, "STATIC_ANALYSIS.md",
                     "census-in-evidence")


def check_memory_audit() -> List[str]:
    """Memory-plan-audit vocabulary: the MemoryAuditReport's frozen key
    sets and classes match deepspeed_tpu/analysis/report.py, every name
    is documented in docs/STATIC_ANALYSIS.md, and docs/AUTOTUNING.md
    cross-links the model_drift calibration record."""
    from deepspeed_tpu.analysis import (BUDGET_KEYS, BUFFER_KEYS,
                                        CALIBRATION_KEYS, MEMORY_CLASSES,
                                        MEMORY_REPORT_KEYS,
                                        MEMORY_TOTALS_KEYS)

    return _vocab_check([
        VocabSpec(name="analysis.MEMORY_REPORT_KEYS",
                  expected=EXPECTED_MEMORY_REPORT_KEYS,
                  actual=lambda: MEMORY_REPORT_KEYS,
                  docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.MEMORY_TOTALS_KEYS",
                  expected=EXPECTED_MEMORY_TOTALS_KEYS,
                  actual=lambda: MEMORY_TOTALS_KEYS,
                  docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.BUFFER_KEYS",
                  expected=EXPECTED_BUFFER_KEYS,
                  actual=lambda: BUFFER_KEYS, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.MEMORY_CLASSES",
                  expected=EXPECTED_MEMORY_CLASSES,
                  actual=lambda: MEMORY_CLASSES, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.BUDGET_KEYS",
                  expected=EXPECTED_BUDGET_KEYS,
                  actual=lambda: BUDGET_KEYS, docs_path=STATIC_DOCS),
        VocabSpec(name="analysis.CALIBRATION_KEYS",
                  expected=EXPECTED_CALIBRATION_KEYS,
                  actual=lambda: CALIBRATION_KEYS, docs_path=STATIC_DOCS),
    ]) + _cross_link(AUTOTUNING_DOCS, "model_drift", "calibration")


def check_recovery() -> List[str]:
    """Recovery vocabulary: the supervisor's frozen state machine matches
    the module and docs/ELASTICITY.md, and the observability doc
    cross-links the elasticity doc from its recovery rows."""
    def _states():
        from deepspeed_tpu.resilience.supervisor import RECOVERY_STATES

        return RECOVERY_STATES

    return _vocab_check([
        VocabSpec(name="supervisor.RECOVERY_STATES",
                  expected=EXPECTED_RECOVERY_STATES, actual=_states,
                  docs_path=ELASTICITY_DOCS),
    ]) + _cross_link(DOCS, "ELASTICITY.md", "recovery")


def check_offload() -> List[str]:
    """Host-tiered offload vocabulary: the chunked config knobs are real
    OffloadOptimizerConfig fields and documented in docs/OFFLOAD.md, and
    the observability doc cross-links the offload doc from its offload
    span rows."""
    from dataclasses import fields as dc_fields

    def _cfg_keys():
        from deepspeed_tpu.runtime.config import OffloadOptimizerConfig

        have = {f.name for f in dc_fields(OffloadOptimizerConfig)}
        return sorted(k for k in OFFLOAD_CONFIG_KEYS if k in have)

    return _vocab_check([
        VocabSpec(name="OffloadOptimizerConfig chunked keys",
                  expected=OFFLOAD_CONFIG_KEYS, actual=_cfg_keys,
                  docs_path=OFFLOAD_DOCS),
    ]) + _cross_link(DOCS, "OFFLOAD.md", "offload")


def check_planner() -> List[str]:
    """Plan-compiler vocabulary: evidence keys / link classes / offload
    tier names match deepspeed_tpu/planner, every name is documented in
    docs/PLANNER.md, and the planner and autotuning docs cross-link
    each other (the Autotuner's planner mode consumes seed_candidates)."""
    from deepspeed_tpu.planner import (LINK_CLASSES, OFFLOAD_TIERS,
                                       PLAN_EVIDENCE_KEYS)

    return _vocab_check([
        VocabSpec(name="planner.PLAN_EVIDENCE_KEYS",
                  expected=EXPECTED_PLAN_EVIDENCE_KEYS,
                  actual=lambda: PLAN_EVIDENCE_KEYS,
                  docs_path=PLANNER_DOCS),
        VocabSpec(name="planner.LINK_CLASSES",
                  expected=EXPECTED_LINK_CLASSES,
                  actual=lambda: LINK_CLASSES, docs_path=PLANNER_DOCS),
        VocabSpec(name="planner offload tiers",
                  expected=EXPECTED_OFFLOAD_TIER_NAMES,
                  actual=lambda: [n for n, _ in OFFLOAD_TIERS],
                  docs_path=PLANNER_DOCS),
    ]) + _cross_link(AUTOTUNING_DOCS, "PLANNER.md", "planner mode") \
       + _cross_link(PLANNER_DOCS, "AUTOTUNING.md", "autotuner handoff")


def check_fleet() -> List[str]:
    """Fleet-observability vocabulary: TierSnapshot schema / SLO ledger
    / request-timeline key sets match their modules, every key is
    documented in docs/OBSERVABILITY.md (per-tier gauges via their
    ``fleet_*_<key>`` wildcard rows), and docs/SERVING.md cross-links
    the fleet section as the autoscaler-input feed."""
    import re

    def _snap_keys():
        from deepspeed_tpu.serving.fleet import (TIER_SNAPSHOT_KEYS,
                                                 TIER_SNAPSHOT_SCHEMA)

        if TIER_SNAPSHOT_SCHEMA != EXPECTED_TIER_SNAPSHOT_SCHEMA:
            raise ValueError(
                f"TIER_SNAPSHOT_SCHEMA is {TIER_SNAPSHOT_SCHEMA}, lint "
                f"pins {EXPECTED_TIER_SNAPSHOT_SCHEMA}")
        return TIER_SNAPSHOT_KEYS

    def _slo(name):
        def thunk():
            import deepspeed_tpu.telemetry.slo as slo

            return getattr(slo, name)
        return thunk

    def _timeline_keys():
        from deepspeed_tpu.serving.disagg import REQUEST_TIMELINE_KEYS

        return REQUEST_TIMELINE_KEYS

    # every tier substitutes into the same gauge wildcard rows: document
    # `fleet_*_queue_depth` once, not once per tier (tier/schema/run_id
    # are identity fields, never exported as gauges)
    gauges = [f"fleet_prefill_{k}" for k in EXPECTED_TIER_SNAPSHOT_KEYS
              if k not in ("tier", "schema", "run_id")]
    return _vocab_check([
        VocabSpec(name="fleet.TIER_SNAPSHOT_KEYS",
                  expected=EXPECTED_TIER_SNAPSHOT_KEYS, actual=_snap_keys,
                  docs_path=DOCS),
        VocabSpec(name="fleet gauges", doc_names=gauges, docs_path=DOCS,
                  doc_normalize=lambda n: re.sub(
                      r"^fleet_(prefill|decode|unified)_", "fleet_*_", n)),
        VocabSpec(name="slo.SLO_TARGET_KEYS",
                  expected=EXPECTED_SLO_TARGET_KEYS,
                  actual=_slo("SLO_TARGET_KEYS"), docs_path=DOCS),
        VocabSpec(name="slo.SLO_BLOCK_KEYS",
                  expected=EXPECTED_SLO_BLOCK_KEYS,
                  actual=_slo("SLO_BLOCK_KEYS"), docs_path=DOCS),
        VocabSpec(name="slo.SLO_SCENARIO_KEYS",
                  expected=EXPECTED_SLO_SCENARIO_KEYS,
                  actual=_slo("SLO_SCENARIO_KEYS"), docs_path=DOCS),
        VocabSpec(name="slo.SLO_LEDGER_KEYS",
                  expected=EXPECTED_SLO_LEDGER_KEYS,
                  actual=_slo("SLO_LEDGER_KEYS"), docs_path=DOCS),
        VocabSpec(name="disagg.REQUEST_TIMELINE_KEYS",
                  expected=EXPECTED_TIMELINE_KEYS, actual=_timeline_keys,
                  docs_path=DOCS),
    ]) + _cross_link(SERVING_DOCS, "OBSERVABILITY.md",
                     "fleet snapshots / autoscaler inputs")


def check_chaos_fleet() -> List[str]:
    """Chaos / self-healing vocabulary: fault kinds, injection points,
    health states and brownout levels match their modules and are
    documented in docs/SERVING.md; and docs/ELASTICITY.md cross-links
    the serving doc from its chaos section (the training and serving
    chaos halves share resilience/chaos.py)."""
    def _kinds():
        from deepspeed_tpu.resilience.chaos import FAULT_KINDS

        return FAULT_KINDS

    def _points():
        from deepspeed_tpu.resilience.chaos import INJECTION_POINTS

        return INJECTION_POINTS

    def _states():
        from deepspeed_tpu.serving.supervisor import HEALTH_STATES

        return HEALTH_STATES

    def _levels():
        from deepspeed_tpu.serving.admission import BROWNOUT_LEVELS

        return BROWNOUT_LEVELS

    return _vocab_check([
        VocabSpec(name="chaos.FAULT_KINDS",
                  expected=EXPECTED_FAULT_KINDS, actual=_kinds,
                  docs_path=SERVING_DOCS),
        VocabSpec(name="chaos.INJECTION_POINTS",
                  expected=EXPECTED_INJECTION_POINTS, actual=_points,
                  docs_path=SERVING_DOCS),
        VocabSpec(name="supervisor.HEALTH_STATES",
                  expected=EXPECTED_HEALTH_STATES, actual=_states,
                  docs_path=SERVING_DOCS),
        VocabSpec(name="admission.BROWNOUT_LEVELS",
                  expected=EXPECTED_BROWNOUT_LEVELS, actual=_levels,
                  docs_path=SERVING_DOCS),
    ]) + _cross_link(ELASTICITY_DOCS, "SERVING.md", "chaos")


def validate_chrome_trace(obj: Any) -> List[str]:
    """Structural validation of a Chrome trace-event JSON object (pass a
    path or the loaded dict).  Perfetto/chrome://tracing both accept the
    object form: ``{"traceEvents": [...]}`` with per-event ``name``,
    ``ph``, ``ts`` (µs), ``pid``/``tid``, and ``dur`` on complete ("X")
    events."""
    if isinstance(obj, str):
        try:
            with open(obj, "r", encoding="utf-8") as f:
                obj = json.load(f)
        except (OSError, ValueError) as e:
            return [f"trace file unreadable / not JSON: {e}"]
    errors: List[str] = []
    if not isinstance(obj, dict) or not isinstance(
            obj.get("traceEvents"), list):
        return ["trace is not an object with a 'traceEvents' list"]
    for i, ev in enumerate(obj["traceEvents"]):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errors.append(f"{where}: missing/empty name")
        ph = ev.get("ph")
        if ph not in ("X", "i", "M"):
            errors.append(f"{where}: unsupported ph {ph!r}")
        if not isinstance(ev.get("ts"), (int, float)) or ev["ts"] < 0:
            errors.append(f"{where}: bad ts {ev.get('ts')!r}")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                errors.append(f"{where}: bad {key} {ev.get(key)!r}")
        if ph == "X" and (not isinstance(ev.get("dur"), (int, float))
                          or ev["dur"] < 0):
            errors.append(f"{where}: X event without valid dur")
        if ph == "X" and not isinstance(
                ev.get("args", {}).get("trace_id"), str):
            errors.append(f"{where}: span without args.trace_id")
    return errors


def check_trace_export() -> List[str]:
    """Generate a sample trace touching every span/event name and assert
    the exported file is well-formed."""
    from deepspeed_tpu.telemetry.tracing import (EVENT_NAMES, SPAN_NAMES,
                                                 Tracer)

    tracer = Tracer(enabled=True)
    tid = tracer.new_trace_id()
    for name in SPAN_NAMES:
        tracer.span(name, tid).set(sample=True).end()
    for name in EVENT_NAMES:
        tracer.instant(name, tid)
    with tempfile.TemporaryDirectory() as d:
        path = tracer.export_chrome_trace(os.path.join(d, "t.trace.json"))
        errors = validate_chrome_trace(path)
        with open(path, "r", encoding="utf-8") as f:
            seen = {ev["name"] for ev in json.load(f)["traceEvents"]
                    if ev.get("ph") in ("X", "i")}
    missing = (set(SPAN_NAMES) | set(EVENT_NAMES)) - seen
    if missing:
        errors.append(f"exported trace lost events: {sorted(missing)}")
    return errors


def run_all() -> List[str]:
    return (check_tags_documented() + check_schema() + check_span_names()
            + check_stage_names() + check_quant_comm() + check_router_serving()
            + check_autotuning() + check_graph_audit()
            + check_memory_audit() + check_offload() + check_recovery()
            + check_planner() + check_fleet() + check_chaos_fleet()
            + check_trace_export())


def main() -> int:
    errors = run_all()
    for e in errors:
        print(f"telemetry_check: ERROR: {e}", file=sys.stderr)
    if not errors:
        print("telemetry_check: OK")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
