"""Benchmark harness — runs on the real TPU chip.

Prints one JSON line per row, with the PRIMARY row last (the driver
records the last line; it carries the full row table under "rows").

Rows (BASELINE.json milestone configs scaled to one chip):
  1. gpt2_350m_zero1   — end-to-end train_batch tokens/s (primary; the
     north star is tokens/sec/chip parity with A100+NCCL ≈ 35k)
  2. llama8b_class_zero3 — Llama-3-8B-geometry layers (full hidden 4096 /
     GQA 32:8 / swiglu 14336) under ZeRO-3 specs, depth scaled to fit one
     chip; tokens/s + MFU
  3. peak_params — largest GPT-class model trained (fwd+bwd+adam) on one
     chip; the top ladder entries use ZeRO-Infinity layer streaming +
     host optimizer state; metric = parameter count
  4. v2_decode — inference v2 fused decode loop tokens/s (paged KV), vs
     the reference FastGen's A100 llama-13B ~52 tok/s/seq class figure
  5. serve_load — the async serving layer (deepspeed_tpu/serving) under
     an open-loop arrival process: tokens/s, p50/p95 TTFT, preemption
     rate; vs_baseline = served tokens/s / one-shot batch generate()
  6. serve_load_multi — the multi-replica tier: a Router over 2 replicas
     on disjoint mesh slices, shared-system-prompt workload with and
     without the paged prefix cache; aggregate tokens/s + p95 TTFT +
     prefix_hit_rate + prefill_tokens_saved
  7. gpt2_350m_autosched — overlap-driven step scheduling: the same
     model/data under the static schedule vs the probe→decide→pin
     autotuned one (autotuning/overlap_scheduler.py); mfu_static vs
     mfu_tuned + the ScheduleDecision evidence that picked the schedule
  8. serve_disagg — disaggregated prefill/decode tiers + speculative
     decoding vs the homogeneous router at a fixed chip budget, under
     the mixed scenario load generator (burst / session_heavy /
     shared_system_prompt / long_prompt_short_decode)

Pass --smoke for a tiny-shape CPU plumbing check (no numbers of record).
Exits non-zero when any row errored.  The parent process stays off JAX:
every row runs in a child of its own, which alone holds the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SMOKE = "--smoke" in sys.argv
if SMOKE:
    # smoke mode is a CPU plumbing check: JAX reads the platform from the
    # environment when its backend first initialises
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")


def _sync(x) -> float:
    """Wait for the device, then fetch the scalar."""
    import jax

    return float(np.asarray(jax.block_until_ready(x)))


def _reset_topology():
    from deepspeed_tpu.parallel import topology

    topology._GLOBAL_TOPOLOGY = None


def _time_train(engine, batch, steps, warmup=3):
    for _ in range(warmup):
        loss = engine.train_batch(batch)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    _sync(loss)
    return time.perf_counter() - t0


def _telemetry_jsonl(name: str) -> str:
    """Per-row StepRecord log path (docs/OBSERVABILITY.md): every bench
    row leaves a machine-readable per-step trail next to its one summary
    number."""
    out_dir = os.environ.get("DSTPU_TELEMETRY_DIR", "./telemetry")
    return os.path.join(out_dir, f"{name}.jsonl")


def _trace_json(name: str) -> str:
    """Per-row Chrome trace-event export (Perfetto-viewable span trace;
    docs/OBSERVABILITY.md 'Tracing & flight recorder')."""
    out_dir = os.environ.get("DSTPU_TELEMETRY_DIR", "./telemetry")
    return os.path.join(out_dir, f"{name}.trace.json")


def _fleet_jsonl(name: str) -> str:
    """Per-row TierSnapshot log (docs/OBSERVABILITY.md 'Fleet snapshots
    & SLO ledger'): one frozen-schema JSON line per tier per sampler
    tick."""
    out_dir = os.environ.get("DSTPU_TELEMETRY_DIR", "./telemetry")
    return os.path.join(out_dir, f"{name}.fleet.jsonl")


def _run_id() -> str:
    """The row's ledger run id (telemetry/ledger.py): ONE id stamped
    through StepRecords, trace metadata, TierSnapshots, and the row's
    manifest so the warehouse can stitch them back together.  main()
    mints one per row into ``DSTPU_RUN_ID`` before the row runs (smoke
    re-exec and subprocess rows inherit it through the environment);
    direct ``--row`` invocations mint their own."""
    return os.environ.get("DSTPU_RUN_ID", "")


def _mint_run_id(name: str) -> str:
    # mirrors telemetry/ledger.py new_run_id WITHOUT importing
    # deepspeed_tpu — the non-smoke parent must stay jax-free so row
    # subprocesses grab the chip cleanly
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    return f"{name}-{stamp}-{os.getpid():x}"


def _telemetry_block(name: str) -> dict:
    return {"enabled": True, "jsonl_path": _telemetry_jsonl(name),
            "run_id": _run_id(),
            "tracing": {"enabled": True, "trace_path": _trace_json(name)}}


def _write_row_manifest(name: str, row: dict) -> dict:
    """Stamp the row with its run_id and write the RunManifest next to
    the row's artifacts (telemetry/ledger.py): the ledger's join point
    between the summary row, the per-step JSONL, the span trace, the
    fleet log, and the SLO block.  Best-effort — a manifest failure must
    never cost the row its number."""
    if "manifest" in row:       # smoke re-exec inner already wrote it
        return row
    rid = _run_id() or _mint_run_id(name)
    row.setdefault("run_id", rid)
    try:
        from deepspeed_tpu.telemetry.ledger import write_manifest

        artifacts = {k: row[k] for k in ("telemetry_jsonl", "trace_json",
                                         "fleet_jsonl", "slo", "flight_dir",
                                         "resolved_config") if k in row}
        out_dir = os.environ.get("DSTPU_TELEMETRY_DIR", "./telemetry")
        row["manifest"] = write_manifest(
            os.path.join(out_dir, f"{name}.manifest.json"),
            name, rid, artifacts, smoke=SMOKE, row=row)
    except Exception as e:      # noqa: BLE001 — diagnostics only
        row.setdefault("manifest_error", str(e)[:160])
    return row


def _span_breakdown(tracer, names) -> dict:
    """Per-phase span-time rollup for a row summary: {phase: total_ms}."""
    summary = tracer.summary()
    return {short: summary.get(name, {}).get("total_ms", 0.0)
            for short, name in names.items()}


def _resolved_config(config: dict, serving: dict = None) -> dict:
    """The row's pinned placement decisions as one machine-readable blob
    written next to the metrics (docs/PLANNER.md "Regression gate"):
    mesh, ZeRO stage, comm wire, step_schedule, offload tier — so the
    planner's known-good gate reads what a row ACTUALLY ran, not a
    hand-copied approximation.  The blob is fragment-shaped: it feeds
    ``planner.rank.plan_rank_of`` directly."""
    z = dict(config.get("zero_optimization") or {})
    out = {
        "mesh": dict(config.get("mesh") or {"data": 1}),
        "train_micro_batch_size_per_gpu": int(
            config.get("train_micro_batch_size_per_gpu", 1)),
        "gradient_accumulation_steps": int(
            config.get("gradient_accumulation_steps", 1)),
        "zero_optimization": {"stage": int(z.get("stage", 0))},
    }
    for key in ("offload_param", "offload_optimizer"):
        if z.get(key):
            out["zero_optimization"][key] = {
                k: v for k, v in dict(z[key]).items()
                if k in ("device", "chunk_bytes", "working_set_bytes")}
    for key in ("comm_quantization", "step_schedule"):
        if config.get(key):
            out[key] = json.loads(json.dumps(config[key]))
    if serving:
        out["serving"] = json.loads(json.dumps(serving))
    return out


# the known-good pinned configs at the canonical 8-chip fleet — single
# source for the planner regression gate (tests/test_planner.py asserts
# each ranks top-3 in its row-mirroring query, planner/audit.py) and for
# the 6.7B offload rung the planner must propose sight-unseen.  Shapes
# mirror the rows' real non-smoke configs above/below.
PINNED_ROW_CONFIGS = {
    "gpt2_350m": {
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 1},
    },
    "gpt2_350m_commquant": {
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 1},
        "comm_quantization": {"enabled": True, "grad_reduce": "int8"},
    },
    "gpt2_350m_autosched": {
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 3},
        "step_schedule": {"mode": "pinned", "gather_prefetch_depth": 2,
                          "param_persistence_threshold": 100_000},
    },
    "longseq_ring": {
        "mesh": {"seq": 8},
        "zero_optimization": {"stage": 2},
    },
    # the peak_params ladder's chunked rung (_PEAK_LADDER
    # gpt2-6.7b-chunked): streamed host params + chunked NVMe optimizer
    "gpt2_6_7b_chunked": {
        "mesh": {"data": 1},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu"},
            "offload_optimizer": {"device": "nvme",
                                  "working_set_bytes": 1 << 30,
                                  "chunk_bytes": 64 << 20}},
    },
}


def _fwd_flops_per_tok(model, seq):
    """Model fwd FLOPs/token: qkvo (GQA-aware) + ffn + lm_head + attn.
    Delegates to telemetry/derive.py — the single home of the MFU math,
    shared with the run ledger's rollups so bench numbers and warehouse
    re-derivations can never disagree.  Import stays function-local:
    rows pin their backend before touching deepspeed_tpu."""
    from deepspeed_tpu.telemetry.derive import fwd_flops_per_tok

    return fwd_flops_per_tok(model, seq)


def _mfu(tokens_per_sec, model, seq):
    # ×3 for fwd+bwd, against the v5e bf16 peak of 197 TFLOP/s
    # (derive.V5E_PEAK_FLOPS_PER_SEC).
    from deepspeed_tpu.telemetry.derive import mfu

    return mfu(tokens_per_sec, model, seq)


def row_gpt2_350m():
    """Primary row — unchanged config from rounds 1-2 for comparability."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    if SMOKE:
        model = get_model_config("gpt2-tiny")
        batch_size, gas, seq, steps = 2, 2, 64, 2
    else:
        # Tuned on-chip: repo Pallas flash attention + dots_flash_saveable
        # remat + gas=8. Ladder: 24.5k → 31.1k → 34.5k → 38.1k → ~40.8k.
        model = get_model_config("gpt2-350m", max_seq_len=1024)
        batch_size, gas, seq, steps = 8, 8, 1024, 8
    config = {
        "train_micro_batch_size_per_gpu": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "dots_flash_saveable"},
        "telemetry": _telemetry_block("gpt2_350m"),
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rows = batch_size * gas
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    dt = _time_train(engine, batch, steps)
    tps = steps * rows * seq / dt
    span_ms = _span_breakdown(engine.telemetry.tracer, {
        "ingest": "train.data_ingest", "dispatch": "train.dispatch",
        "sync": "train.sync"})
    engine.destroy()
    _reset_topology()
    # Baseline: GPT-2 350M-class on one A100, eager torch+DeepSpeed ZeRO-1,
    # ≈35k tokens/s (bf16, seq 1024): A100 312 TFLOPs at ~40% MFU.
    return {
        "metric": "gpt2_350m_zero1_train_tokens_per_sec_per_chip",
        "value": round(tps, 1), "unit": "tokens/s",
        "vs_baseline": round(tps / 35_000.0, 3),
        "mfu": round(_mfu(tps, model, seq), 3),
        "telemetry_jsonl": _telemetry_jsonl("gpt2_350m"),
        "trace_json": _trace_json("gpt2_350m"),
        "span_ms": span_ms,
        "resolved_config": _resolved_config(config),
    }


def _commquant_once(wire: str, steps: int):
    """One comm-quant training run: explicit quantized DP grad reduce with
    ``wire`` on the wire (comm/quantized.py), fixed data, returns
    (tokens/s/chip, per-step losses, grad-reduce wire bytes)."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.comm.quantized import QUANT_COMM_OPS
    from deepspeed_tpu.models import get_model_config

    n = jax.device_count()
    if SMOKE:
        model = get_model_config("gpt2-tiny", num_layers=2)
        batch_size, gas, seq, run_steps = 1, 2, 32, max(3, steps)
    else:
        model = get_model_config("gpt2-350m", max_seq_len=1024)
        batch_size, gas, seq, run_steps = 8, 8, 1024, steps
    name = f"gpt2_350m_commquant_{wire}"
    config = {
        "train_micro_batch_size_per_gpu": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": not SMOKE},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "mesh": {"data": n},
        "comm_quantization": {"enabled": True, "grad_reduce": wire},
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "dots_flash_saveable"},
        "telemetry": _telemetry_block(name),
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    assert engine._comm_quant is not None, "explicit reduce path not active"
    rows = batch_size * gas * engine.topology.dp_size
    rng = np.random.default_rng(0)  # IDENTICAL data across wire dtypes
    ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1),
                       dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    losses = [_sync(engine.train_batch(batch)) for _ in range(run_steps)]
    # the loss loop above compiled + warmed the step; warmup=1 re-syncs
    dt = _time_train(engine, batch, run_steps, warmup=1)
    comm = engine._comm_delta()
    grad_bytes = sum(comm.get(op, {}).get("bytes", 0)
                     for op in QUANT_COMM_OPS)
    engine.destroy()
    _reset_topology()
    tps = run_steps * rows * seq / dt / max(1, n)
    return tps, losses, grad_bytes, _resolved_config(config)


def _commquant_body():
    """Comm-quant variant of the gpt2_350m row: the SAME model/step with
    the DP gradient reduction routed through the explicit collective path
    (comm_quantization), int8 wire vs an explicit-fp32-wire control.
    Verification rides the per-collective comm-volume telemetry: the row
    reports the measured grad-reduce byte reduction AND the N-step
    loss-curve delta vs the fp32 reduce (docs/QUANTIZED_COMM.md)."""
    steps = 3 if SMOKE else 8
    tps_q, losses_q, bytes_q, resolved = _commquant_once("int8", steps)
    tps_f, losses_f, bytes_f, _ = _commquant_once("fp32", steps)
    loss_delta = max(abs(a - b) for a, b in zip(losses_q, losses_f))
    return {
        "metric": "gpt2_350m_commquant_int8_train_tokens_per_sec_per_chip",
        "value": round(tps_q, 1), "unit": "tokens/s",
        # quantized wire vs the explicit fp32-wire control (same schedule)
        "vs_baseline": round(tps_q / tps_f, 3) if tps_f else 0.0,
        "grad_reduce_bytes_fp32": int(bytes_f),
        "grad_reduce_bytes_quant": int(bytes_q),
        "bytes_reduction": round(bytes_f / bytes_q, 2) if bytes_q else 0.0,
        "loss_delta": round(loss_delta, 5),
        "loss_final_fp32": round(losses_f[-1], 5),
        "loss_final_int8": round(losses_q[-1], 5),
        "telemetry_jsonl": _telemetry_jsonl("gpt2_350m_commquant_int8"),
        "trace_json": _trace_json("gpt2_350m_commquant_int8"),
        "resolved_config": resolved,
    }


def row_gpt2_350m_commquant():
    """Quantized-collective row.  Explicit DP grad reduce needs dp > 1;
    smoke mode pins the in-process backend to ONE cpu device, so the
    smoke variant re-execs itself on a virtual 8-device CPU mesh (same
    pattern as longseq_ring)."""
    if SMOKE and "--commquant-inner" not in sys.argv:
        import os
        import subprocess

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, __file__, "--row", "gpt2_350m_commquant",
               "--smoke", "--commquant-inner"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900, env=env)
        except subprocess.TimeoutExpired:
            return {"metric": "gpt2_350m_commquant", "error": "smoke timed out"}
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"metric": "gpt2_350m_commquant",
                "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}
    return _commquant_body()


def _autosched_run(model, config, batch, steps, seq):
    """One training run for the autosched A/B → (tokens/s/chip, losses)."""
    import jax

    import deepspeed_tpu as ds

    engine, _, _, _ = ds.initialize(model=model, config=config)
    rows = next(iter(batch.values())).shape[0]
    losses = [_sync(engine.train_batch(batch)) for _ in range(steps)]
    dt = _time_train(engine, batch, steps, warmup=1)
    engine.destroy()
    _reset_topology()
    tps = steps * rows * seq / dt / max(1, jax.device_count())
    return tps, losses


def _autosched_fused_ab(model, static_cfg, batch, steps, seq):
    """Fused-vs-scheduled gather A/B → the frozen
    fused_gather_loss_delta / fused_gather_wire_bytes keys.  Both sides
    run IDENTICAL data; the fused engine's all-gather wire bytes come
    from the static census (analysis.collective_census_engine)."""
    import copy

    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.auditor import collective_census_engine

    def variant(fused):
        cfg = copy.deepcopy(static_cfg)
        cfg["zero_optimization"] = {
            **cfg.get("zero_optimization", {}),
            "param_persistence_threshold": 0}
        cfg["step_schedule"] = {"gather_prefetch_depth": 2,
                                "fused_gather_matmul": fused}
        return cfg

    engine, _, _, _ = ds.initialize(model=model, config=variant(False))
    losses_sched = [_sync(engine.train_batch(batch)) for _ in range(steps)]
    engine.destroy()
    _reset_topology()

    engine, _, _, _ = ds.initialize(model=model, config=variant(True))
    assert engine.model_config.fused_gather_matmul, \
        "fused gather-matmul gate did not engage"
    losses_fused = [_sync(engine.train_batch(batch)) for _ in range(steps)]
    census = collective_census_engine(engine)
    assert census["fused_collective"]["gather_matmul"]["present"]
    gather_bytes = int(census.get("all-gather", {}).get("wire_bytes", 0))
    engine.destroy()
    _reset_topology()
    return {
        "fused_gather_loss_delta": round(
            max(abs(a - b) for a, b in zip(losses_fused, losses_sched)),
            6),
        "fused_gather_wire_bytes": gather_bytes,
    }


def _autosched_body():
    """Overlap-driven step scheduling (autotuning/overlap_scheduler.py;
    docs/AUTOTUNING.md): the SAME model/data trained under the static
    schedule vs the probe→decide→pin autotuned one.  The probe runs k
    steps under a forced telemetry capture, the decision table picks the
    schedule from the overlap report, and the tuned run executes from
    the pinned ``step_schedule`` block — the row reports both MFUs, the
    exposed-comm evidence, and the decision(s) that fired.  On the CPU
    smoke mesh the XPlane report degrades to the software-span estimate
    (the decision loop is what's validated, not chip timings) and the
    overlap threshold is forced to 1.0 so a decision deterministically
    fires."""
    import jax

    from deepspeed_tpu.autotuning.overlap_scheduler import ensure_schedule
    from deepspeed_tpu.models import get_model_config

    n = jax.device_count()
    if SMOKE:
        model = get_model_config("gpt2-tiny", num_layers=2)
        batch_size, gas, seq, steps = 1, 2, 32, 3
        probe_steps, threshold = 2, 1.0
    else:
        model = get_model_config("gpt2-350m", max_seq_len=1024)
        batch_size, gas, seq, steps = 8, 8, 1024, 8
        probe_steps, threshold = 3, 0.5
    name = "gpt2_350m_autosched"
    # ZeRO-3: the issue's success metric is MFU on the ZeRO-3 row — the
    # stage whose param gathers the zero3_prefetch decision reschedules
    base = {
        "train_micro_batch_size_per_gpu": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": not SMOKE},
        "zero_optimization": {"stage": 3},
        "gradient_clipping": 1.0,
        "mesh": {"data": n},
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "dots_flash_saveable"},
        "telemetry": _telemetry_block(name),
        "step_schedule": {"mode": "probe", "probe_steps": probe_steps,
                          "overlap_threshold": threshold},
    }
    rows = batch_size * gas * n
    rng = np.random.default_rng(0)  # IDENTICAL data for probe + both runs
    ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1),
                       dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}

    static_cfg = {k: v for k, v in base.items() if k != "step_schedule"}
    tps_static, losses_s = _autosched_run(model, static_cfg, batch, steps,
                                          seq)

    tuned_cfg, decisions = ensure_schedule(model, base, batch)
    assert tuned_cfg["step_schedule"]["mode"] == "pinned"
    tps_tuned, losses_t = _autosched_run(model, tuned_cfg, batch, steps, seq)

    # fused-vs-scheduled gather A/B (the fused_gather_matmul decision
    # arm's two sides on identical data; docs/AUTOTUNING.md): scheduled
    # = prefetch-depth-2 unroll, fused = the gather-matmul MLP region
    # (ops/pallas/gather_matmul.py).  Persistence is forced off so the
    # MLP weights actually shard at smoke geometry (the 350m row's MLP
    # crosses the default threshold on its own).
    fused_ab = _autosched_fused_ab(model, static_cfg, batch, steps, seq)

    fired = sorted({d.decision for d in decisions} - {"noop"})
    ev = decisions[0].evidence
    return {
        "metric": "gpt2_350m_autosched_train_tokens_per_sec_per_chip",
        "value": round(tps_tuned, 1), "unit": "tokens/s",
        # tuned schedule vs the static control (same data, same silicon)
        "vs_baseline": round(tps_tuned / tps_static, 3) if tps_static
        else 0.0,
        "mfu_static": round(_mfu(tps_static, model, seq), 6),
        "mfu_tuned": round(_mfu(tps_tuned, model, seq), 6),
        "exposed_comm_ms": ev["exposed_comm_ms"],
        "schedule_decision": "+".join(fired) if fired else "noop",
        "overlap_fraction": ev["overlap_fraction"],
        "overlap_source": ev["overlap_source"],
        "decisions": [d.to_dict() for d in decisions],
        "loss_final_static": round(losses_s[-1], 5),
        "loss_final_tuned": round(losses_t[-1], 5),
        **fused_ab,
        "telemetry_jsonl": _telemetry_jsonl(name),
        "trace_json": _trace_json(name),
        "resolved_config": _resolved_config(tuned_cfg),
    }


def row_gpt2_350m_autosched():
    """Overlap-scheduler row.  The decision paths need dp > 1; smoke mode
    pins the in-process backend to ONE cpu device, so the smoke variant
    re-execs itself on a virtual 8-device CPU mesh (same pattern as
    gpt2_350m_commquant)."""
    if SMOKE and "--autosched-inner" not in sys.argv:
        import subprocess

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, __file__, "--row", "gpt2_350m_autosched",
               "--smoke", "--autosched-inner"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900, env=env)
        except subprocess.TimeoutExpired:
            return {"metric": "gpt2_350m_autosched",
                    "error": "smoke timed out"}
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"metric": "gpt2_350m_autosched",
                "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}
    return _autosched_body()


def row_llama8b_class_zero3():
    """Llama-3-8B geometry (hidden 4096, GQA 32:8, swiglu 14336) with depth
    and vocab scaled to one chip, ZeRO-3 sharding specs active
    (single-device: specs are trivial but the code path — fsdp param style
    + streamed update — is the 8B-on-v5e-8 configuration of BASELINE.json).

    Sizing: AdamW keeps fp32 master+m+v = 12 B/param persistent, and the
    measured program peak is ~21 B/param; one 15.75-GB v5e chip therefore
    caps this row near 750M params.  Full 128256 vocab alone is 1.05G
    params (embed+head), so the vocab is cut to 32256 and depth to 2 —
    the per-layer geometry (the thing MFU depends on) is untouched.
    Measured r04: 35,968 tok/s = 63.2% MFU."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    if SMOKE:
        # loss_tiles mirrors the real row so the ZeRO-3 + tiled-loss
        # combination smoke-compiles before the driver's on-chip run
        model = get_model_config("llama-tiny", loss_tiles=4)
        batch_size, gas, seq, steps, layers = 2, 1, 64, 2, 2
    else:
        layers = 2
        batch_size, gas, seq, steps = 8, 8, 1024, 4
        model = get_model_config("llama3-8b", num_layers=layers,
                                 vocab_size=32256, max_seq_len=seq,
                                 loss_tiles=8)
    config = {
        "train_micro_batch_size_per_gpu": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "dots_flash_saveable"},
        "telemetry": _telemetry_block("llama8b_class_zero3"),
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rows = batch_size * gas
    rng = np.random.default_rng(1)
    ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    seq_eff = min(seq, model.max_seq_len)
    dt = _time_train(engine, batch, steps)
    tps = steps * rows * seq_eff / dt
    engine.destroy()
    _reset_topology()
    # A100 80G, Llama-class layers, ZeRO-3 bf16: ~55% MFU published for
    # well-tuned stacks ⇒ per-chip token rate for THIS depth:
    a100_tps = 0.55 * 312e12 / (3 * _fwd_flops_per_tok(model, seq_eff))
    return {
        "metric": f"llama3_8b_class_{layers}L_zero3_tokens_per_sec_per_chip",
        "value": round(tps, 1), "unit": "tokens/s",
        "vs_baseline": round(tps / a100_tps, 3),
        "mfu": round(_mfu(tps, model, seq_eff), 3),
        "telemetry_jsonl": _telemetry_jsonl("llama8b_class_zero3"),
        "trace_json": _trace_json("llama8b_class_zero3"),
        "resolved_config": _resolved_config(config),
    }


def _longseq_row(model, seed: int, label: str, steps: int = 3):
    """Shared long-context training body: one chip, seq 32k through the
    KV-blocked Pallas flash path with sequence-tiled logits+loss (ALST)
    so [B,S,V] never materialises.  flash_saveable, not
    dots_flash_saveable: at seq 32k the saved matmul outputs alone are
    ~15GB (measured r04: 21.8G > 15.75G); saving only the flash
    residuals fits with room to spare.  vs_baseline = MFU / 0.55
    (blogs/ulysses-offload long-context claim)."""
    import deepspeed_tpu as ds

    batch_size, gas = 1, 2
    seq = model.max_seq_len
    config = {
        "train_micro_batch_size_per_gpu": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "flash_saveable"},
        "telemetry": _telemetry_block(f"longseq_{label}"),
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rows = batch_size * gas
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1),
                       dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    dt = _time_train(engine, batch, steps, warmup=2)
    tps = steps * rows * seq / dt
    engine.destroy()
    _reset_topology()
    mfu = _mfu(tps, model, seq)
    return {
        "metric": f"longseq_{seq}_{label}_train_tokens_per_sec_per_chip",
        "value": round(tps, 1), "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.55, 3),
        "mfu": round(mfu, 3),
        "telemetry_jsonl": _telemetry_jsonl(f"longseq_{label}"),
        "trace_json": _trace_json(f"longseq_{label}"),
        "resolved_config": _resolved_config(config),
    }


def row_longseq_flash():
    """Long-context row, d=64 MHA class (gpt2-350m at seq 32k): the
    config held since r03 for cross-round comparability.  d=64 heads cap
    the MXU contraction at half utilization — see row_longseq_llama for
    the like-for-like comparison against the reference claim."""
    from deepspeed_tpu.models import get_model_config

    if SMOKE:
        model = get_model_config("gpt2-tiny", max_seq_len=256, loss_tiles=4)
        return _longseq_row(model, 2, "flash", steps=2)
    model = get_model_config("gpt2-350m", max_seq_len=32768,
                             loss_tiles=32, attn_impl="pallas_flash")
    return _longseq_row(model, 2, "flash")


def row_longseq_llama():
    """Long-context row at the reference claim's model class: d=128 GQA
    llama geometry (h=2048, 16:8 heads, swiglu 8192, 6L) at seq 32k.
    The reference's 55%-MFU FPDT claim is on GPT/Llama-class models with
    128-wide heads (blogs/ulysses-offload/README.md:47-48), where the
    flash kernel runs 113.4 TF/s fwd+bwd vs 57.8 at d=64 (r04 sweep)."""
    from deepspeed_tpu.models import get_model_config

    if SMOKE:
        model = get_model_config("llama-tiny", max_seq_len=256, loss_tiles=4)
        return _longseq_row(model, 4, "llama_d128", steps=2)
    model = get_model_config(
        "llama3-8b", hidden_size=2048, num_heads=16, num_kv_heads=8,
        intermediate_size=8192, num_layers=6, vocab_size=32256,
        max_seq_len=32768, loss_tiles=32, attn_impl="pallas_flash")
    return _longseq_row(model, 4, "llama_d128")


def _ring_wire_ab():
    """Per-hop fused-vs-scheduled wire A/B (comm_quantization.
    ring_rotation; docs/RING_ATTENTION.md): int8 quantized rotation vs
    the fp32 wire.  Wire bytes are CENSUS-verified via
    analysis.collective_census_engine on twin engines (the static HLO
    parse of every collective-permute — the ratio is geometry-
    independent, so the census twins stay small), and loss parity runs
    on IDENTICAL data at a long-sequence smoke (per-position V-wire
    noise enters the loss ~1/S, so the longseq regime is where the row
    lives anyway) with fp32 compute so the delta is pure wire error."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.auditor import collective_census_engine
    from deepspeed_tpu.models import get_model_config

    def build(wire, seq):
        model = get_model_config("llama-tiny", max_seq_len=seq,
                                 seq_impl="ring",
                                 ring_placement="striped",
                                 attn_impl="xla")
        cfg = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2},
            "gradient_clipping": 1.0,
            "mesh": {"seq": 4},
            "steps_per_print": 10_000,
        }
        if wire != "fp32":
            cfg["comm_quantization"] = {"enabled": True,
                                        "ring_rotation": wire}
        engine, _, _, _ = ds.initialize(model=model, config=cfg)
        return engine, model

    wire_bytes = {}
    for wire in ("fp32", "int8"):
        engine, _ = build(wire, 256)
        census = collective_census_engine(engine)
        wire_bytes[wire] = int(census.get("collective-permute",
                                          {}).get("wire_bytes", 0))
        if wire == "int8":
            fused = census["fused_collective"]["ring_rotation"]
            assert fused["present"] and fused["wire"] == "int8", fused
        engine.destroy()
        _reset_topology()

    seq, steps = 2048, 2
    losses = {}
    for wire in ("fp32", "int8"):
        engine, model = build(wire, seq)
        rows = engine.topology.dp_size
        rng = np.random.default_rng(6)  # IDENTICAL data across wires
        ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1),
                           dtype=np.int32)
        batch = {"input_ids": ids[:, :-1],
                 "labels": ids[:, 1:].astype(np.int32)}
        losses[wire] = [_sync(engine.train_batch(batch))
                        for _ in range(steps)]
        engine.destroy()
        _reset_topology()

    loss_delta = max(abs(a - b) for a, b in zip(losses["int8"],
                                                losses["fp32"]))
    return {
        "ring_wire_bytes_fp32": wire_bytes["fp32"],
        "ring_wire_bytes_quant": wire_bytes["int8"],
        "ring_wire_reduction": round(
            wire_bytes["fp32"] / wire_bytes["int8"], 2)
        if wire_bytes["int8"] else 0.0,
        "ring_loss_delta": round(loss_delta, 6),
    }


def _longseq_ring_body():
    """Ring context parallelism measured for real: llama-class geometry
    with the sequence sharded over a "seq" mesh ring — striped block
    placement (causal load balance), the Pallas flash inner block on TPU,
    ZeRO-2 composed on top (the exact composition the remat fix in
    sequence/ring.py + runtime/engine.py targets).  Reports
    tokens/s/chip; vs_baseline = MFU / 0.55 like the other longseq rows."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    n = jax.device_count()
    if SMOKE:
        sp = min(4, n)
        model = get_model_config("llama-tiny", max_seq_len=256,
                                 seq_impl="ring", ring_placement="striped",
                                 attn_impl="xla")
        batch_size, gas, steps, warmup = 2, 1, 2, 1
        mesh = {"seq": sp}
        # route the ring inner block through the interpreted Pallas
        # kernels so the smoke run exercises the FUSED fwd+bwd ring path
        # (on TPU _kernel_enabled() selects it natively)
        import importlib

        importlib.import_module(
            "deepspeed_tpu.ops.pallas.flash_mha").INTERPRET = True
    else:
        # d=128 GQA llama geometry (the longseq_llama row's model) with the
        # 32k sequence sharded over every chip in one ring
        sp = n
        model = get_model_config(
            "llama3-8b", hidden_size=2048, num_heads=16, num_kv_heads=8,
            intermediate_size=8192, num_layers=6, vocab_size=32256,
            max_seq_len=32768, loss_tiles=32, seq_impl="ring",
            ring_placement="striped", attn_impl="pallas_flash")
        batch_size, gas, steps, warmup = 1, 2, 3, 2
        mesh = {"seq": sp}
    config = {
        "train_micro_batch_size_per_gpu": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "gradient_clipping": 1.0,
        "mesh": mesh,
        "steps_per_print": 10_000,
        "telemetry": _telemetry_block("longseq_ring"),
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    seq = model.max_seq_len
    dp = engine.topology.dp_size
    rows = batch_size * dp * gas
    rng = np.random.default_rng(6)
    ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1),
                       dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    dt = _time_train(engine, batch, steps, warmup=warmup)
    tps_chip = steps * rows * seq / dt / max(1, n)
    engine.destroy()
    _reset_topology()
    mfu = _mfu(tps_chip, model, seq)
    from deepspeed_tpu.sequence.ring import _kernel_enabled

    ring_bwd = "fused" if _kernel_enabled() else "xla"
    # quantize-into-ppermute A/B (after the main engine is torn down —
    # the A/B builds its own twins); the XLA wire codec is gate-
    # independent, so drop the smoke's interpreter flag first: the
    # interpreted Pallas kernels at the A/B's 2048-seq loss run would
    # crawl, and the wire bytes/parity they'd measure are identical
    if SMOKE:
        import importlib

        importlib.import_module(
            "deepspeed_tpu.ops.pallas.flash_mha").INTERPRET = False
    wire_ab = _ring_wire_ab()
    return {
        "metric": f"longseq_{seq}_ring_sp{sp}_train_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1), "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.55, 3),
        "mfu": round(mfu, 3),
        "placement": "striped",
        "ring_backward": ring_bwd,
        **wire_ab,
        "telemetry_jsonl": _telemetry_jsonl("longseq_ring"),
        "trace_json": _trace_json("longseq_ring"),
        "resolved_config": _resolved_config(config),
    }


def row_longseq_ring():
    """Ring-attention long-context row.  The ring needs sp > 1; smoke mode
    pins the in-process backend to ONE cpu device, so the smoke variant
    re-execs itself on a virtual 8-device CPU mesh (same pattern as the
    driver's row isolation)."""
    if SMOKE and "--ring-inner" not in sys.argv:
        import os
        import subprocess

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, __file__, "--row", "longseq_ring",
               "--smoke", "--ring-inner"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900, env=env)
        except subprocess.TimeoutExpired:
            return {"metric": "longseq_ring", "error": "smoke timed out"}
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"metric": "longseq_ring",
                "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}
    return _longseq_ring_body()


# Peak-params ladder: (name, base preset, model overrides, zero_config).
# Big entries lean on the framework's own scale machinery — ZeRO-Infinity
# layer streaming (offload_param cpu: layer weights live host-side,
# streamed through the compiled scan) + host optimizer state — because
# plain AdamW is 12 B/param of persistent HBM (so one bare 15.75-GB v5e
# chip caps near 750M params).  This is a fits-and-trains metric (one
# finite step), not throughput, so host-transfer latency is acceptable.
# entries: (name, base config, overrides, zero config, subprocess timeout).
# NVMe rungs put the fp32 masters+moments (and the streamed param
# partition) on DISK via NVMeOptimizerSwapper + pipelined reads — the
# repo's ZeRO-Infinity tier (ref swap_tensor/partitioned_optimizer_
# swapper.py:27) — so host RAM stops being the wall that killed the
# 4B/6.7B cpu rungs in r04 (RESOURCE_EXHAUSTED on ~80GB hosts).
_PEAK_LADDER = [
    ("gpt2-8b-nvme", "gpt2-1.3b",
     dict(hidden_size=4096, intermediate_size=16384, num_layers=40,
          num_heads=32, max_seq_len=512),
     {"stage": 3, "offload_param": {"device": "nvme"},
      "offload_optimizer": {"device": "nvme"}}, 1500.0),
    # the 6.7B chunked rung: streamed host params (offload_param cpu) +
    # the chunked host Adam with its masters+moments on DISK
    # (offload_optimizer nvme + working_set_bytes) — host RAM holds only
    # the streamed param partition and O(chunk) optimizer working set,
    # so the ~80GB host that killed the r04 cpu rung suffices
    ("gpt2-6.7b-chunked", "gpt2-1.3b",
     dict(hidden_size=4096, intermediate_size=16384, num_layers=32,
          num_heads=32, max_seq_len=512),
     {"stage": 3, "offload_param": {"device": "cpu"},
      "offload_optimizer": {"device": "nvme",
                            "working_set_bytes": 1 << 30,
                            "chunk_bytes": 64 << 20}}, 1500.0),
    ("gpt2-6.7b-nvme", "gpt2-1.3b",
     dict(hidden_size=4096, intermediate_size=16384, num_layers=32,
          num_heads=32, max_seq_len=512),
     {"stage": 3, "offload_param": {"device": "nvme"},
      "offload_optimizer": {"device": "nvme"}}, 1200.0),
    ("gpt2-4b-nvme", "gpt2-1.3b",
     dict(hidden_size=3072, intermediate_size=12288, num_layers=36,
          num_heads=24, max_seq_len=512),
     {"stage": 3, "offload_param": {"device": "nvme"},
      "offload_optimizer": {"device": "nvme"}}, 900.0),
    # cpu (host-RAM) rungs: 6.7B needs ~120GB of remote-host RAM for the
    # fp32 masters+moments (observed r04: compiles and streams, dies
    # RESOURCE_EXHAUSTED at runtime) — the 4B rung fits a ~80GB host
    # cpu-chunked: masters stay host-RESIDENT but the step runs over
    # 64MB chunks with double-buffered d2h/h2d, so transfer working set
    # is O(chunk) and the host Adam overlaps the streams
    ("gpt2-4b-stream", "gpt2-1.3b",
     dict(hidden_size=3072, intermediate_size=12288, num_layers=36,
          num_heads=24, max_seq_len=512),
     {"stage": 3, "offload_param": {"device": "cpu"},
      "offload_optimizer": {"device": "cpu",
                            "working_set_bytes": 8 << 30,
                            "chunk_bytes": 64 << 20}}, 700.0),
    ("gpt2-2.7b-stream", "gpt2-1.3b",
     dict(hidden_size=2560, intermediate_size=10240, num_layers=32,
          num_heads=32, max_seq_len=512),
     {"stage": 3, "offload_param": {"device": "cpu"},
      "offload_optimizer": {"device": "cpu"}}, 600.0),
    ("gpt2-1.3b-offload", "gpt2-1.3b", dict(max_seq_len=512),
     {"stage": 2, "offload_optimizer": {"device": "cpu"}}, 600.0),
    ("gpt2-774m", "gpt2-350m",
     dict(hidden_size=1600, num_layers=24, num_heads=20, max_seq_len=512),
     {"stage": 0}, 600.0),
]


def _host_ram_bytes() -> int:
    """Host RAM — the budget cpu-offloaded classes must fit (the
    offload rungs die in HOST RESOURCE_EXHAUSTED — r04).  Priced against
    MemAvailable (what the kernel can actually hand out) minus a 10%
    safety margin, NOT MemTotal: on a busy host the page cache and other
    tenants hold a big slice of MemTotal, and a rung admitted against
    the total dies RESOURCE_EXHAUSTED mid-ladder anyway.  Falls back to
    MemTotal, then 16 GiB."""
    total = avail = 0
    try:
        with open("/proc/meminfo", "r", encoding="utf-8") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    avail = int(line.split()[1]) * 1024
                elif line.startswith("MemTotal:"):
                    total = int(line.split()[1]) * 1024
    except OSError:
        pass
    if avail:
        return int(avail * 0.9)
    return total or (16 << 30)


def _host_peak_bytes() -> int:
    """Measured host high-water mark (VmHWM) of THIS process — the
    measured counterpart the ladder records next to the predictor's
    `predicted_peak_bytes` (read via the CPU accelerator's /proc
    watermark so bench and telemetry agree on the source)."""
    try:
        from deepspeed_tpu.accelerator.cpu_accelerator import \
            CPU_Accelerator

        return int(CPU_Accelerator().memory_stats(0).get(
            "peak_bytes_in_use", 0))
    except Exception:
        return 0


def _memory_budget_bytes() -> int:
    """The budget the ladder's DEVICE-resident state must fit: the chip's
    HBM limit — asked of a child, because the peak_params row starts
    --peak-entry children that need the chip and so must not initialise
    a JAX backend itself — or host RAM in smoke mode (the CPU backend's
    "device" memory IS host RAM)."""
    if SMOKE:
        return _host_ram_bytes()
    from deepspeed_tpu.utils.platform import device_facts_from_child

    return int(device_facts_from_child()["bytes_limit"])


def _peak_rungs():
    """(name, base, overrides, zero, seq) per ladder rung.  The smoke
    ladder runs three tiny rungs so the plumbing check actually
    EXECUTES every optimizer tier — fused on-device, cpu-chunked host
    Adam, and the nvme chunk store — not just the base path."""
    if SMOKE:
        nvme_dir = os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "dstpu_bench_nvme_smoke")
        return [
            ("gpt2-tiny", "gpt2-tiny", {}, {"stage": 0}, 64),
            ("gpt2-tiny-cpu-chunk", "gpt2-tiny", {},
             {"stage": 2,
              "offload_optimizer": {"device": "cpu",
                                    "working_set_bytes": 1,
                                    "chunk_bytes": 1 << 16}}, 64),
            ("gpt2-tiny-nvme-chunk", "gpt2-tiny", {},
             {"stage": 2,
              "offload_optimizer": {"device": "nvme",
                                    "nvme_path": nvme_dir,
                                    "working_set_bytes": 1,
                                    "chunk_bytes": 1 << 16}}, 64),
        ]
    return [(name, base, over, zero, 512)
            for name, base, over, zero, _ in _PEAK_LADDER]


def _ladder_predictions() -> list:
    """OOM-before-you-run gate (docs/STATIC_ANALYSIS.md): the calibrated
    analytic predictor prices every rung BEFORE anything runs, so a
    too-big rung reports why it cannot fit (dominant class + shortfall)
    instead of dying in RESOURCE_EXHAUSTED mid-ladder."""
    from deepspeed_tpu.autotuning import (ModelInfo,
                                          load_memory_calibration,
                                          predict_fit)
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.profiling import get_model_profile

    budget = _memory_budget_bytes()
    cal = load_memory_calibration(backend="cpu" if SMOKE else "tpu")
    preds = []
    for name, base, over, zero, seq in _peak_rungs():
        model = get_model_config(base, **over)
        prof = get_model_profile(model, 1, seq)
        # offloaded classes must not be priced against the device
        # budget (they are the POINT of the offload rungs) — cpu-homed
        # state is priced against host RAM instead, nvme is unbounded
        off_p = (zero.get("offload_param") or {}).get("device")
        off_o_cfg = zero.get("offload_optimizer") or {}
        off_o = off_o_cfg.get("device")
        # chunked rungs price the O(chunk) pinned working set instead of
        # the whole fp32 state (the nvme tier's host need IS the chunk)
        chunk = (off_o_cfg.get("chunk_bytes")
                 if off_o_cfg.get("working_set_bytes") else None)
        pred = predict_fit(
            ModelInfo(num_params=prof["params"],
                      hidden_size=model.hidden_size,
                      num_layers=model.num_layers,
                      vocab_size=model.vocab_size),
            int(zero.get("stage", 0)), dp_size=1, micro_batch=1,
            seq_len=seq, hbm_bytes=budget, calibration=cal,
            offload_param=off_p, offload_optimizer=off_o,
            chunk_bytes=chunk,
            host_bytes=_host_ram_bytes()
            if ("cpu" in (off_p, off_o) or chunk) else None)
        preds.append({
            "rung": name,
            "predicted_peak_bytes": pred["predicted_peak_bytes"],
            "predicted_fit": pred["predicted_fit"],
            "dominant_class": pred["dominant_class"],
            "shortfall_bytes": pred["shortfall_bytes"],
        })
    return preds


def _peak_entry(idx: int) -> dict:
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    if SMOKE:
        name, base, over, zero, seq = _peak_rungs()[idx]
    else:
        name, base, over, zero, _ = _PEAK_LADDER[idx]
        seq = 512
    model = get_model_config(base, **over)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": zero,
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "nothing_saveable"},
        "telemetry": _telemetry_block("peak_params"),
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, model.vocab_size, size=(1, seq + 1),
                       dtype=np.int32)
    batch = {"input_ids": ids[:, :-1],
             "labels": ids[:, 1:].astype(np.int32)}
    loss = engine.train_batch(batch)
    if not np.isfinite(_sync(loss)):
        raise RuntimeError("non-finite loss")
    import jax

    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(engine.params))
    entry = {"name": name, "params_m": round(n_params / 1e6, 1),
             # measured VmHWM next to the predictor's number — the
             # ladder's predicted-vs-measured host story per rung
             "host_peak_bytes": _host_peak_bytes(),
             "offload_overlap_fraction":
                 getattr(engine, "_last_offload_overlap", None)}
    if SMOKE:
        # smoke runs every rung in ONE process — tear down between rungs
        # or the next engine inherits this one's mesh and swap pools
        engine.destroy()
        _reset_topology()
    return entry


def row_peak_params():
    """Largest model trained end-to-end (fwd+bwd+adam step) on one chip —
    the 'train bigger than you think' metric.  The ladder consults the
    static memory predictor FIRST (per-rung `predicted_peak_bytes` /
    `predicted_fit` — a rung predicted not to fit is skipped with its
    dominant class + shortfall recorded instead of dying in
    RESOURCE_EXHAUSTED; DSTPU_PEAK_RUN_ALL=1 overrides).  Each attempted
    entry runs in its own subprocess (an OOM-killed entry must not leak
    HBM into the next); largest that completes a finite step wins."""
    preds = _ladder_predictions()
    run_all = os.environ.get("DSTPU_PEAK_RUN_ALL") == "1"
    best = None
    best_idx = None
    if SMOKE:
        # run EVERY smoke rung (base, cpu-chunked, nvme-chunked) so the
        # plumbing check exercises all three optimizer tiers; the base
        # rung stays the reported metric for comparability
        for i in range(len(preds)):
            entry = _peak_entry(i)
            preds[i]["ran"] = True
            preds[i]["fit"] = True
            preds[i]["host_peak_bytes"] = entry["host_peak_bytes"]
            preds[i]["offload_overlap_fraction"] = \
                entry["offload_overlap_fraction"]
            if best is None:
                best = entry
                best_idx = i
    else:
        import subprocess

        for i in range(len(_PEAK_LADDER)):
            preds[i]["ran"] = False
            preds[i]["fit"] = None
            if not preds[i]["predicted_fit"] and not run_all:
                continue   # the predictor already explains why
            preds[i]["ran"] = True
            try:
                proc = subprocess.run(
                    [sys.executable, __file__, "--peak-entry", str(i)],
                    capture_output=True, text=True,
                    timeout=_PEAK_LADDER[i][4])
            except subprocess.TimeoutExpired:
                preds[i]["fit"] = False
                continue
            for line in reversed(proc.stdout.strip().splitlines()):
                if line.startswith("{") and "params_m" in line:
                    best = json.loads(line)
                    break
            preds[i]["fit"] = best is not None
            if best:
                preds[i]["host_peak_bytes"] = best.get("host_peak_bytes")
                preds[i]["offload_overlap_fraction"] = \
                    best.get("offload_overlap_fraction")
                best_idx = i
                break
    if best is None:
        raise RuntimeError("no ladder entry fit")
    # A100-80G fits ~1.3B params trained in fp32-master Adam without
    # offload (16 bytes/param ≈ 21GB + activations); the reference's
    # ZeRO-Offload headline is 13B on one V100-32G — scale by HBM
    # (v5e 16GB → 6.5B-class) for the offload-assisted bar.
    return {
        "metric": "peak_params_trained_one_chip",
        "value": best["params_m"], "unit": "Mparams",
        "vs_baseline": round(best["params_m"] / 6500.0, 3),
        "model": best["name"],
        "predicted_peak_bytes": preds[best_idx]["predicted_peak_bytes"],
        "predicted_fit": preds[best_idx]["predicted_fit"],
        "host_peak_bytes": best.get("host_peak_bytes"),
        "offload_overlap_fraction": best.get("offload_overlap_fraction"),
        "ladder": preds,
        "telemetry_jsonl": _telemetry_jsonl("peak_params"),
        "trace_json": _trace_json("peak_params"),
        "resolved_config": _resolved_config({
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "zero_optimization": _peak_rungs()[best_idx][3]}),
    }


def _v2_decode_once(model, eng_cfg, n_seqs, gen_tokens, prompt_len=32):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    eng = InferenceEngineV2(model, eng_cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, model.vocab_size, size=(prompt_len,)).tolist()
               for _ in range(n_seqs)]
    # warmup with the full token budget: compiles every decode-chunk
    # bucket the timed run will use (a chunk size first seen inside the
    # timing window would bill its remote compile as decode time)
    eng.generate(prompts, max_new_tokens=gen_tokens)
    eng.generate(prompts, max_new_tokens=1)
    # prefill throughput: admit + first token for all prompts (SplitFuse
    # mixed steps with on-device sampling)
    t0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=1)
    prefill_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=gen_tokens)
    dt = time.perf_counter() - t0
    # steady-state decode: the 1-token run above paid the same prefill, so
    # the difference times only the remaining gen_tokens-1 decode steps
    decode_dt = max(dt - prefill_dt, 1e-9)
    _reset_topology()
    return (n_seqs * (gen_tokens - 1) / decode_dt,
            n_seqs * prompt_len / prefill_dt)


def row_v2_decode():
    """Inference v2 fused decode loop (paged KV cache): steady-state decode
    tokens/s on one chip, bf16 cache and int8 (quantized-KV) cache."""
    from deepspeed_tpu.models import get_model_config

    if SMOKE:
        model = get_model_config("llama-tiny")
        n_seqs, gen_tokens = 2, 8
        eng_cfg = {}
    else:
        model = get_model_config("llama3-8b", num_layers=4, max_seq_len=2048)
        # 32 seqs ride the 64-slot decode batch, and 128-step fused chunks
        # amortize the per-dispatch host round-trip (measured r04: 64
        # active seqs raised tok/s only 21% — the step is compute-bound —
        # while doubling the bar, so 32 is the better operating point)
        n_seqs, gen_tokens = 32, 128
        eng_cfg = {"max_decode_chunk": 128,
                   "memory_config": {"num_blocks": 1024}}
    tps, prefill_tps = _v2_decode_once(model, eng_cfg, n_seqs, gen_tokens)
    int8_cfg = {**eng_cfg,
                "memory_config": {**eng_cfg.get("memory_config", {}),
                                  "kv_dtype": "int8"}}
    tps_int8, _ = _v2_decode_once(model, int8_cfg, n_seqs, gen_tokens)
    best = max(tps, tps_int8)
    # FastGen blog: Llama-13B-class full-depth decode on A100 ≈ 50
    # tok/s/seq; scale the bar by PARAM count, not layer count — decode
    # cost tracks weight bytes/FLOPs, and the 525M-param lm_head (full
    # 128256 vocab) does not shrink when depth is truncated.
    layer_p = 218.1e6  # one llama3-8b layer (GQA attn 41.9M + swiglu 176.2M)
    embed_p = 2 * 128256 * 4096
    n_p = embed_p + model.num_layers * layer_p
    full_p = embed_p + 32 * layer_p
    bar_per_seq = 50.0 * (full_p / n_p)
    # Decode is HBM-bandwidth-bound (weights + KV re-read per token), so
    # the cross-hardware bar must be normalized by the bandwidth ratio:
    # v5e ≈ 0.82 TB/s vs A100-80G ≈ 2.0 TB/s → 0.41.  vs_baseline is the
    # raw param-scaled FastGen bar; vs_roofline divides out the hardware
    # ratio (1.0 = "as good as the reference, per byte/s of HBM").
    hw_bw_ratio = 0.82 / 2.0
    vs_raw = best / (bar_per_seq * n_seqs)
    # the decode engine has no serve loop to stream records from; emit
    # one summary StepRecord so this row leaves a JSONL trail too
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry

    tel = Telemetry(TelemetryConfig(
        enabled=True, jsonl_path=_telemetry_jsonl("v2_decode"),
        run_id=_run_id()))
    tel.record_serving_step(0, {
        "tokens_out": n_seqs * gen_tokens, "tokens_per_sec": best,
        "bf16_tokens_per_sec": tps, "int8_kv_tokens_per_sec": tps_int8,
        "prefill_tokens_per_sec": prefill_tps})
    tel.close()
    return {
        "metric": "v2_decode_tokens_per_sec",
        "telemetry_jsonl": _telemetry_jsonl("v2_decode"),
        "value": round(best, 1), "unit": "tokens/s",
        "vs_baseline": round(vs_raw, 3),
        "vs_roofline": round(vs_raw / hw_bw_ratio, 3),
        "bf16_tokens_per_sec": round(tps, 1),
        "int8_kv_tokens_per_sec": round(tps_int8, 1),
        "prefill_tokens_per_sec": round(prefill_tps, 1),
        "resolved_config": _resolved_config(
            {}, serving={"n_replicas": 1, "engine": eng_cfg}),
    }


def row_serve_load():
    """Serving layer (deepspeed_tpu/serving) under a synthetic open-loop
    arrival process: requests arrive on an exponential clock regardless of
    service progress (the closed-loop alternative hides queueing delay),
    stream through the async serve loop, and the row reports delivered
    tokens/s, p50/p95 TTFT, and the preemption rate.  vs_baseline is the
    serving path's throughput against the same engine's one-shot batch
    generate() on the identical workload — the async layer's overhead
    (queue, admission, per-step host fan-out) expressed as a fraction."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    if SMOKE:
        model = get_model_config("llama-tiny")
        n_req, new, prompt_len, rate = 8, 8, 16, 100.0
        # 31 usable blocks vs 8 requests × 6 final blocks: admission
        # overcommits and the smoke run exercises real preemption
        eng_cfg = {"dtype": "float32",
                   "memory_config": {"num_blocks": 32, "block_size": 4},
                   "max_context": 64}
    else:
        model = get_model_config("llama3-8b", num_layers=4, max_seq_len=2048)
        n_req, new, prompt_len, rate = 64, 64, 32, 32.0
        eng_cfg = {"memory_config": {"num_blocks": 1024}}
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry

    tel = Telemetry(TelemetryConfig(
        enabled=True, jsonl_path=_telemetry_jsonl("serve_load"),
        run_id=_run_id(),
        tracing={"enabled": True, "trace_path": _trace_json("serve_load")}))
    eng = InferenceEngineV2(model, eng_cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, model.vocab_size, size=(prompt_len,)).tolist()
               for _ in range(n_req)]
    # baseline + warmup in one: batch one-shot generate compiles every
    # bucket the served run will hit, and times the non-serving path
    eng.generate(prompts, max_new_tokens=new)
    t0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=new)
    batch_dt = time.perf_counter() - t0
    batch_tps = n_req * new / batch_dt

    srv = InferenceServer(eng, {"metrics_interval_steps": 32},
                          telemetry=tel).start()
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
    t0 = time.perf_counter()
    streams = []
    for i in range(n_req):
        lag = arrivals[i] - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        streams.append(srv.submit(prompts[i],
                                  SamplingParams(max_new_tokens=new)))
    for s in streams:
        s.result()
    dt = time.perf_counter() - t0
    srv.stop()
    snap = srv.metrics.snapshot()
    span_ms = _span_breakdown(tel.tracer, {
        "queue": "serve.queue_wait", "prefill": "serve.prefill",
        "decode": "serve.decode"})
    tel.close()
    _reset_topology()
    tps = n_req * new / dt
    return {
        "metric": "serve_load_tokens_per_sec",
        "telemetry_jsonl": _telemetry_jsonl("serve_load"),
        "trace_json": _trace_json("serve_load"),
        "span_ms": span_ms,
        "value": round(tps, 1), "unit": "tokens/s",
        "vs_baseline": round(tps / batch_tps, 3),
        "ttft_p50_ms": round(snap["ttft"]["p50"] * 1e3, 1),
        "ttft_p95_ms": round(snap["ttft"]["p95"] * 1e3, 1),
        "tpot_p50_ms": round(snap["tpot"]["p50"] * 1e3, 2),
        "preemption_rate": round(snap["preemptions"] / n_req, 3),
        "completed": snap["completed"],
        "resolved_config": _resolved_config(
            {}, serving={"n_replicas": 1, "engine": eng_cfg}),
    }


def _serve_load_multi_body():
    """Multi-replica serving tier (serving/replica.py + router.py +
    prefix_cache.py): a mixed scenario schedule (shared_system_prompt +
    session_heavy traffic mixes from the scenario load generator)
    against a Router over 2 replicas on DISJOINT virtual mesh slices.
    Two sub-runs on identical workloads — prefix reuse ON vs OFF — report
    aggregate delivered tokens/s and p95 TTFT (measured router-side:
    submit → first token on the routed stream), plus the cache's
    hit-rate and prefill-tokens-saved counters.  Frozen keys linted by
    tools/telemetry_check.py against docs/SERVING.md."""
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.serving import ReplicaSet, Router
    from deepspeed_tpu.telemetry import Telemetry

    n_rep = 2
    if SMOKE:
        model = get_model_config("llama-tiny")
        n_per_mix, rate = 6, 100.0
        eng_cfg = {"dtype": "float32",
                   "memory_config": {"num_blocks": 64, "block_size": 4},
                   "max_context": 64}
    else:
        model = get_model_config("llama3-8b", num_layers=4,
                                 max_seq_len=2048)
        n_per_mix, rate = 64, 64.0
        eng_cfg = {"memory_config": {"num_blocks": 1024}}
    rng = np.random.default_rng(11)
    # the cache-relevant half of the scenario vocabulary: one shared
    # system prompt across everyone + session-sticky per-session prefixes
    schedule = _scenario_schedule(("shared_system_prompt",
                                   "session_heavy"), rng, model,
                                  n_per_mix, rate, SMOKE)
    warm_prompts = [r["prompt"] for r in schedule[:n_rep]]

    def run_once(prefix_enabled, telemetry=None):
        srv_cfg = {"prefix_cache": {"enabled": prefix_enabled}}
        rs = ReplicaSet.build(model, n_rep, eng_cfg, srv_cfg, seed=0)
        router = Router(rs, telemetry=telemetry).start()
        # warmup: compile every replica's buckets off the clock
        router.generate(warm_prompts, max_new_tokens=8)
        # baseline the cache counters so the reported hit rate / tokens
        # saved cover only the measured window (warmup hits the cache too)
        warm = rs.snapshot()
        res = _drive_schedule(router, schedule)
        snap = router.snapshot()
        for key in ("prefix_hits", "prefix_misses", "prefill_tokens_saved"):
            snap["aggregate"][key] -= warm[key]
        router.stop()
        _reset_topology()
        return res, snap

    tel = Telemetry(TelemetryConfig(
        enabled=True, jsonl_path=_telemetry_jsonl("serve_load_multi"),
        run_id=_run_id(),
        tracing={"enabled": True,
                 "trace_path": _trace_json("serve_load_multi")}))
    # reuse run FIRST: the second run inherits this process's warm XLA
    # compile cache, so running the no-reuse control second biases the
    # comparison AGAINST the cache — the reported win is conservative
    res_on, snap = run_once(True, telemetry=tel)
    res_off, _ = run_once(False)
    tel.close()
    tps_on, p95_on = res_on["tokens_per_sec"], res_on["ttft_p95_ms"]
    tps_off, p95_off = res_off["tokens_per_sec"], res_off["ttft_p95_ms"]
    agg = snap["aggregate"]
    hits, misses = agg["prefix_hits"], agg["prefix_misses"]
    return {
        "metric": "serve_load_multi_tokens_per_sec",
        "telemetry_jsonl": _telemetry_jsonl("serve_load_multi"),
        "trace_json": _trace_json("serve_load_multi"),
        "value": round(tps_on, 1), "unit": "tokens/s",
        "agg_tokens_per_sec": round(tps_on, 1),
        "agg_tokens_per_sec_noreuse": round(tps_off, 1),
        # reuse vs no-reuse on the identical workload
        "vs_baseline": round(tps_on / tps_off, 3) if tps_off else 0.0,
        "ttft_p95_ms": round(p95_on, 1),
        "ttft_p95_ms_noreuse": round(p95_off, 1),
        # frozen-key SLO ledger block (telemetry/slo.py SLO_BLOCK_KEYS):
        # attainment over the reuse run's per-request measurements, with
        # per-scenario-phase attainment under by_scenario
        "slo": _slo_spec().evaluate(res_on["requests"]),
        "prefix_hit_rate": round(hits / max(1, hits + misses), 3),
        "prefill_tokens_saved": int(agg["prefill_tokens_saved"]),
        "n_replicas": n_rep,
        "routed": snap["routed"],
        "failovers": snap["failovers"],
        "resolved_config": _resolved_config(
            {}, serving={"n_replicas": n_rep,
                         "prefix_cache": {"enabled": True}}),
    }


def row_serve_load_multi():
    """Multi-replica serving row.  Disjoint replica slices need > 1
    device; smoke mode pins the in-process backend to ONE cpu device,
    so the smoke variant re-execs itself on a virtual 8-device CPU mesh
    (same pattern as longseq_ring)."""
    if SMOKE and "--multi-inner" not in sys.argv:
        import os
        import subprocess

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, __file__, "--row", "serve_load_multi",
               "--smoke", "--multi-inner"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900, env=env)
        except subprocess.TimeoutExpired:
            return {"metric": "serve_load_multi",
                    "error": "smoke timed out"}
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"metric": "serve_load_multi",
                "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}
    return _serve_load_multi_body()


# ---------------------------------------------------------------------------
# Scenario load generator (docs/SERVING.md "Scenario load generator"):
# named traffic mixes composed into one open-loop schedule.  The mix
# names are a frozen vocabulary linted by tools/telemetry_check.py.
# ---------------------------------------------------------------------------

SCENARIO_MIXES = ("burst", "session_heavy", "shared_system_prompt",
                  "long_prompt_short_decode")


def _scenario_requests(mix: str, rng, model, n_req: int, rate: float,
                       smoke: bool) -> list:
    """One named traffic mix → request dicts {at, prompt, max_new,
    session, mix}.  Shapes scale with --smoke; arrival processes are the
    point: `burst` clusters arrivals (queue-depth stress),
    `session_heavy` pins few sessions with per-session shared prefixes
    (sticky-routing + cache stress), `shared_system_prompt` shares one
    long system prefix across everyone (the dominant production shape),
    and `long_prompt_short_decode` is prefill-dominated (the mix that
    separates the tiers)."""
    if mix not in SCENARIO_MIXES:
        raise ValueError(f"unknown scenario mix {mix!r} "
                         f"(known: {SCENARIO_MIXES})")
    vocab = model.vocab_size
    toks = lambda n: rng.integers(1, vocab, size=n).tolist()
    out = []
    if mix == "burst":
        group, uniq, new = (4, 10, 6) if smoke else (16, 64, 32)
        for i in range(n_req):           # exactly n_req, last burst may
            g = i // group               # be partial
            at0 = g * (group / rate) * 4.0   # bursts with idle gaps
            out.append({"at": at0 + rng.uniform(0, 0.002),
                        "prompt": toks(uniq), "max_new": new,
                        "session": None, "mix": mix})
    elif mix == "session_heavy":
        n_sessions = max(2, n_req // 3)
        uniq, new = (4, 6) if smoke else (24, 48)
        prefix_len = 8 if smoke else 256
        prefixes = [toks(prefix_len) for _ in range(n_sessions)]
        at = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
        for i in range(n_req):
            s = int(rng.integers(0, n_sessions))
            out.append({"at": float(at[i]),
                        "prompt": prefixes[s] + toks(uniq),
                        "max_new": new, "session": f"sess-{s}",
                        "mix": mix})
    elif mix == "shared_system_prompt":
        sys_len, uniq, new = (16, 6, 6) if smoke else (512, 32, 48)
        system = toks(sys_len)
        at = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
        for i in range(n_req):
            out.append({"at": float(at[i]), "prompt": system + toks(uniq),
                        "max_new": new, "session": None, "mix": mix})
    else:  # long_prompt_short_decode
        new = 4 if smoke else 8
        at = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
        for i in range(n_req):
            plen = int(rng.integers(24, 33)) if smoke \
                else int(rng.integers(1024, 1537))
            out.append({"at": float(at[i]), "prompt": toks(plen),
                        "max_new": new, "session": None, "mix": mix})
    return out


def _scenario_schedule(mixes, rng, model, n_per_mix: int, rate: float,
                       smoke: bool) -> list:
    """Compose named mixes into ONE merged arrival schedule (sorted by
    arrival time — the mixes interleave, they don't run back-to-back)."""
    sched = []
    for mix in mixes:
        sched.extend(_scenario_requests(mix, rng, model, n_per_mix,
                                        rate, smoke))
    sched.sort(key=lambda r: r["at"])
    return sched


def _drive_schedule(router, schedule, speculative: bool = False,
                    timeout: float = 600.0) -> dict:
    """Open-loop drive of one schedule against a router front door.
    Measures router-side per-request TTFT and TPOT (first/last token
    wall times observed by a consumer thread per stream) and aggregate
    delivered tokens/s."""
    import threading

    from deepspeed_tpu.serving import SamplingParams

    n = len(schedule)
    first_at = [0.0] * n
    last_at = [0.0] * n
    counts = [0] * n
    threads, streams = [], []

    def consume(i, stream):
        for _tok in stream:
            now = time.perf_counter()
            if first_at[i] == 0.0:
                first_at[i] = now
            last_at[i] = now
            counts[i] += 1

    t0 = time.perf_counter()
    for i, req in enumerate(schedule):
        lag = req["at"] - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        s = router.submit(req["prompt"],
                          SamplingParams(max_new_tokens=req["max_new"],
                                         speculative=speculative),
                          session=req["session"])
        streams.append(s)
        th = threading.Thread(target=consume, args=(i, s))
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=timeout)
    dt = time.perf_counter() - t0
    submit_at = [t0 + r["at"] for r in schedule]
    ttft_ms = sorted((f - s) * 1e3 for f, s in zip(first_at, submit_at)
                     if f > 0)
    tpot_ms = sorted((l - f) / (c - 1) * 1e3
                     for f, l, c in zip(first_at, last_at, counts)
                     if c > 1 and f > 0)

    # shared percentile derivation (telemetry/derive.py) — same index
    # formula the run ledger uses when it re-rolls these artifacts
    from deepspeed_tpu.telemetry.derive import p95

    handoff_ms = sorted(s.handoff_ms for s in streams
                        if getattr(s, "handoff_ms", None) is not None)
    handoff_bytes = [s.handoff_bytes for s in streams
                     if getattr(s, "handoff_bytes", None) is not None]
    # per-request measurements keyed by scenario mix — the SLO
    # evaluator's input (telemetry/slo.py SLOSpec.evaluate)
    requests = [{
        "scenario": r["mix"],
        "ttft_ms": ((first_at[i] - submit_at[i]) * 1e3
                    if first_at[i] > 0 else None),
        "tpot_ms": ((last_at[i] - first_at[i]) / (counts[i] - 1) * 1e3
                    if counts[i] > 1 and first_at[i] > 0 else None),
    } for i, r in enumerate(schedule)]
    return {
        "tokens_per_sec": sum(counts) / dt,
        "ttft_p95_ms": p95(ttft_ms), "tpot_p95_ms": p95(tpot_ms),
        "delivered": sum(counts), "completed": sum(1 for s in streams
                                                   if s.error is None),
        "handoff_ms_p95": p95(handoff_ms),
        "handoff_bytes_per_req": (sum(handoff_bytes)
                                  / max(1, len(handoff_bytes))),
        "requests": requests,
    }


def _slo_spec():
    """The bench rows' SLO targets (serving.slo shape): generous enough
    that a healthy CPU-smoke run attains them, tight enough that a
    regression (a stuck tier, a starved queue) shows as burn.  The
    prefill-dominated mix gets a looser TTFT target — exactly what
    scenario_overrides exists for."""
    from deepspeed_tpu.telemetry.slo import SLOSpec

    t = ({"ttft_p95_ms": 20_000.0, "tpot_p95_ms": 10_000.0,
          "queue_wait_p95_ms": 20_000.0} if SMOKE
         else {"ttft_p95_ms": 2_000.0, "tpot_p95_ms": 250.0,
               "queue_wait_p95_ms": 1_000.0})
    return SLOSpec({"enabled": True, "objective": 0.99, **t,
                    "scenario_overrides": {
                        "long_prompt_short_decode":
                            {"ttft_p95_ms": 2 * t["ttft_p95_ms"]}}})


def _serve_disagg_body():
    """Disaggregated tiers vs the homogeneous router at a FIXED chip
    budget (serving/disagg.py; docs/SERVING.md "Disaggregated tiers &
    speculative decoding"): the same mixed scenario schedule — every
    named mix, dominated by long_prompt_short_decode + chat-heavy
    session traffic — drives (a) a DisaggRouter over 2 prefill + 2
    decode replicas with KV-block handoff and speculative decoding on
    the decode tier, and (b) a plain Router over 4 unified replicas on
    the identical 4×2-device slices.  Frozen keys linted by
    tools/telemetry_check.py against docs/SERVING.md."""
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.serving import DisaggRouter, ReplicaSet, Router
    from deepspeed_tpu.telemetry import Telemetry

    if SMOKE:
        model = get_model_config("llama-tiny", num_layers=2)
        n_per_mix, rate = 5, 50.0
        eng_cfg = {"dtype": "float32",
                   "memory_config": {"num_blocks": 96, "block_size": 4},
                   "max_context": 64}
    else:
        model = get_model_config("llama3-8b", num_layers=4,
                                 max_seq_len=2048)
        n_per_mix, rate = 32, 48.0
        eng_cfg = {"memory_config": {"num_blocks": 1024}}
    # identical-architecture draft (same seed ⇒ same argmax): the row
    # measures the serving-stack term of speculation — accepted tokens
    # per dispatch at its ceiling — because the draft-quality term needs
    # a trained/distilled draft checkpoint the bench does not have
    # (random-weight heterogeneous drafts agree at ~1/vocab chance)
    draft = model
    rng = np.random.default_rng(15)
    schedule = _scenario_schedule(SCENARIO_MIXES, rng, model, n_per_mix,
                                  rate, SMOKE)
    mix_counts = {m: sum(1 for r in schedule if r["mix"] == m)
                  for m in SCENARIO_MIXES}
    srv_cfg = {"prefix_cache": {"enabled": True},
               "metrics_window_s": 60.0}
    # warm set spans the shape buckets: a couple of typical prompts plus
    # one long-prompt entry (its block-table bucket compiles separately)
    warm = [r["prompt"] for r in schedule[:2]]
    warm.append(next(r["prompt"] for r in schedule
                     if r["mix"] == "long_prompt_short_decode"))

    tel = Telemetry(TelemetryConfig(
        enabled=True, jsonl_path=_telemetry_jsonl("serve_disagg"),
        run_id=_run_id(),
        tracing={"enabled": True,
                 "trace_path": _trace_json("serve_disagg")}))

    # (a) disaggregated: 2 prefill + 2 decode tiers + spec decoding
    disagg = {"enabled": True, "prefill_replicas": 2,
              "decode_replicas": 2,
              "speculative": {"enabled": True, "draft_model": draft,
                              "spec_k": 3}}
    rs = ReplicaSet.build(model, 4, eng_cfg, srv_cfg, seed=0,
                          disagg=disagg)
    router = DisaggRouter(rs, telemetry=tel).start()
    # compile off the clock: speculative submits so the draft + verify-k
    # buckets (not just prefill/decode) are warm before the window opens
    from deepspeed_tpu.serving import FleetSampler
    from deepspeed_tpu.serving import SamplingParams as _SP
    for s in [router.submit(p, _SP(max_new_tokens=6, speculative=True))
              for p in warm]:
        s.result(timeout=600)
    sampler = FleetSampler(rs, router=router, slo=_slo_spec(),
                           cadence_s=0.25,
                           jsonl_path=_fleet_jsonl("serve_disagg"),
                           telemetry=tel).start()
    dis = _drive_schedule(router, schedule, speculative=True)
    snap = router.snapshot()
    sampler.stop()                 # quiesce the cadence thread first so
    sampler.sample_once()          # the tail tick is the true last row
    fleet = sampler.latest()
    router.stop()
    _reset_topology()
    tel.close()

    # (b) homogeneous control: the same 8 chips as 4 unified replicas
    rs_h = ReplicaSet.build(model, 4, eng_cfg, srv_cfg, seed=0)
    router_h = Router(rs_h).start()
    router_h.generate(warm, max_new_tokens=6)
    hom = _drive_schedule(router_h, schedule, speculative=False)
    router_h.stop()
    _reset_topology()

    return {
        "metric": "serve_disagg_tokens_per_sec",
        "telemetry_jsonl": _telemetry_jsonl("serve_disagg"),
        "trace_json": _trace_json("serve_disagg"),
        "value": round(dis["tokens_per_sec"], 1), "unit": "tokens/s",
        "agg_tokens_per_sec_disagg": round(dis["tokens_per_sec"], 1),
        "agg_tokens_per_sec_homog": round(hom["tokens_per_sec"], 1),
        "vs_baseline": (round(dis["tokens_per_sec"]
                              / hom["tokens_per_sec"], 3)
                        if hom["tokens_per_sec"] else 0.0),
        "ttft_p95_ms_disagg": round(dis["ttft_p95_ms"], 1),
        "ttft_p95_ms_homog": round(hom["ttft_p95_ms"], 1),
        "tpot_p95_ms_disagg": round(dis["tpot_p95_ms"], 2),
        "tpot_p95_ms_homog": round(hom["tpot_p95_ms"], 2),
        "handoff_ms_p95": round(dis["handoff_ms_p95"], 2),
        "handoff_bytes_per_req": round(dis["handoff_bytes_per_req"], 1),
        "handoffs": snap["handoffs"],
        "spec_accept_rate": round(
            snap["aggregate"]["spec_accept_rate"], 3),
        # frozen-key SLO ledger block (telemetry/slo.py SLO_BLOCK_KEYS)
        # with per-scenario-phase attainment under by_scenario
        "slo": _slo_spec().evaluate(dis["requests"]),
        "fleet_jsonl": _fleet_jsonl("serve_disagg"),
        "fleet_tiers": sorted(fleet),
        "scenario_mix": mix_counts,
        "completed_disagg": dis["completed"],
        "completed_homog": hom["completed"],
        "resolved_config": _resolved_config(
            {}, serving={"n_replicas": 4,
                         "disagg": {"enabled": True,
                                    "prefill_replicas": 2,
                                    "decode_replicas": 2,
                                    "speculative": True, "spec_k": 3}}),
    }


def row_serve_disagg():
    """Disaggregated-serving row.  Tier slices need 8 devices; smoke
    mode pins ONE cpu device, so the smoke variant re-execs itself on a
    virtual 8-device CPU mesh (same pattern as serve_load_multi)."""
    if SMOKE and "--disagg-inner" not in sys.argv:
        import os
        import subprocess

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, __file__, "--row", "serve_disagg",
               "--smoke", "--disagg-inner"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900, env=env)
        except subprocess.TimeoutExpired:
            return {"metric": "serve_disagg", "error": "smoke timed out"}
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"metric": "serve_disagg",
                "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}
    return _serve_disagg_body()


def _chaos_train_half(base: str, tel) -> dict:
    """Train-side chaos (resilience/supervisor.py): an unkilled reference
    run, then the same workload with a worker killed mid-train AND its
    host removed from the survivors census — the supervisor must dump a
    flight bundle, stop the group (SIGTERM→SIGKILL budget), re-plan a
    SMALLER mesh, restart, and resume from the latest committed
    universal checkpoint with the loss curve landing back on the
    reference."""
    from deepspeed_tpu.resilience.supervisor import (RecoverySupervisor,
                                                     loss_curve)

    if SMOKE:
        total_steps, die_at, deadline_s = 6, 3, 240.0
        n_hosts, dev_per_host = 2, 2
        worker_env = {"DSTPU_SEQ": "16", "DSTPU_BATCH": "8"}
    else:
        total_steps, die_at, deadline_s = 20, 10, 600.0
        n_hosts, dev_per_host = 2, 4
        worker_env = {"DSTPU_SEQ": "128", "DSTPU_BATCH": "8"}

    ref_dir = os.path.join(base, "ref")
    sup_ref = RecoverySupervisor(
        ref_dir, hosts_fn=lambda: [f"h{i}" for i in range(n_hosts)],
        devices_per_host=dev_per_host, total_steps=total_steps,
        deadline_s=60.0, poll_s=0.2, worker_env=dict(worker_env),
        force_cpu=SMOKE)
    ref = sup_ref.run()
    ref_losses = loss_curve(ref.progress_path)

    chaos_dir = os.path.join(base, "chaos")
    os.makedirs(chaos_dir, exist_ok=True)
    sentinel = os.path.join(chaos_dir, ".chaos_fired")

    def hosts():
        # the dying worker arms the chaos sentinel just before exiting —
        # from then on host h1 is gone and the re-plan must shrink
        alive = n_hosts - (1 if os.path.exists(sentinel) else 0)
        return [f"h{i}" for i in range(alive)]

    sup = RecoverySupervisor(
        chaos_dir, hosts_fn=hosts, devices_per_host=dev_per_host,
        total_steps=total_steps, deadline_s=60.0, poll_s=0.2,
        stop_timeout_s=15.0, resume_deadline_s=deadline_s, telemetry=tel,
        worker_env={**worker_env,
                    "DSTPU_CHAOS": json.dumps({"die_at": die_at})},
        force_cpu=SMOKE)
    res = sup.run()
    curve = loss_curve(res.progress_path)

    gap = max(abs(curve[s] - ref_losses[s])
              for s in ref_losses if s >= die_at)
    recovery_s = res.outages[0]["outage_s"] if res.outages else -1.0
    assert res.returncode == 0 and res.recoveries >= 1, \
        (res.returncode, res.recoveries)
    assert res.outages and res.outages[0]["resized"], \
        "host loss did not shrink the planned mesh"
    assert recovery_s < deadline_s, (recovery_s, deadline_s)
    # one outage = one skipped record next to total_steps applied ones
    goodput_after = total_steps / (total_steps + len(res.outages))
    return {"recovery_s": round(recovery_s, 1),
            "loss_gap": round(gap, 6),
            "goodput_after": round(goodput_after, 4),
            "recovered_mesh": res.outages[0]["mesh"],
            "flight_bundle": bool(res.outages[0]["bundle"])}


def _chaos_serve_half() -> dict:
    """Serving-side chaos: open-loop load against a 2-replica Router;
    replica r0 is hard-killed mid-load (fail-over must keep p99 TTFT
    bounded and every request completing) and then respawned LIVE on its
    own slice (ReplicaSet.respawn) — the re-grown replica must serve
    again."""
    import threading

    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.serving import ReplicaSet, Router, SamplingParams

    model = get_model_config("llama-tiny")
    if SMOKE:
        n_req, new, rate = 12, 8, 50.0
        eng_cfg = {"dtype": "float32",
                   "memory_config": {"num_blocks": 64, "block_size": 4},
                   "max_context": 64}
    else:
        n_req, new, rate = 64, 32, 32.0
        eng_cfg = {"memory_config": {"num_blocks": 512}}
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, model.vocab_size, size=12).tolist()
               for _ in range(n_req)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
    kill_at, respawn_at = n_req // 3, 2 * n_req // 3

    rs = ReplicaSet.build(model, 2, eng_cfg, {}, seed=0)
    router = Router(rs).start()
    router.generate(prompts[:2], max_new_tokens=new)  # compile both
    first_at = [0.0] * n_req
    threads = []

    def consume(i, stream):
        for _tok in stream:
            if first_at[i] == 0.0:
                first_at[i] = time.perf_counter()

    t0 = time.perf_counter()
    for i in range(n_req):
        lag = arrivals[i] - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        if i == kill_at:
            rs[0].kill()          # hard stop: in-flight requests fail over
        if i == respawn_at:
            rs.respawn(0)         # live re-grow on the freed slice
        s = router.submit(prompts[i], SamplingParams(max_new_tokens=new))
        th = threading.Thread(target=consume, args=(i, s))
        th.start()
        threads.append(th)
    submit_at = [t0 + a for a in arrivals]
    for th in threads:
        th.join(timeout=600)
    ttft_ms = sorted((f - s) * 1e3
                     for f, s in zip(first_at, submit_at) if f > 0)
    p99 = (ttft_ms[min(len(ttft_ms) - 1, int(0.99 * (len(ttft_ms) - 1)))]
           if ttft_ms else -1.0)
    snap = router.snapshot()
    # the respawned replica must actually serve: a direct request to it
    out = rs[0].server.generate([prompts[0]], max_new_tokens=4)
    regrown = int(rs[0].alive and len(out[0]) == 4)
    router.stop()
    _reset_topology()
    assert len(ttft_ms) == n_req, (len(ttft_ms), n_req)
    assert snap["failovers"] >= 1, snap
    assert regrown == 1
    return {"serve_ttft_p99_ms": round(p99, 1),
            "failovers": int(snap["failovers"]),
            "regrown": regrown}


def _chaos_recovery_body():
    """Chaos row (docs/ELASTICITY.md): kill a worker mid-train → assert
    recovery within the deadline + loss continuity on a SHRUNK mesh;
    kill a serving replica under open-loop load → assert p99 TTFT stays
    bounded through fail-over and the ReplicaSet re-grows live.  Frozen
    keys linted by tools/telemetry_check.py against docs/ELASTICITY.md."""
    import tempfile

    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry

    base = tempfile.mkdtemp(prefix="dstpu_chaos_")
    tel = Telemetry(TelemetryConfig(
        enabled=True, jsonl_path=_telemetry_jsonl("chaos_recovery"),
        run_id=_run_id(),
        tracing={"enabled": True,
                 "trace_path": _trace_json("chaos_recovery")},
        flight={"enabled": True,
                "output_dir": os.path.join(base, "flight")}))
    train = _chaos_train_half(base, tel)
    serve = _chaos_serve_half()
    tel.close()
    return {
        "metric": "chaos_recovery_s",
        "telemetry_jsonl": _telemetry_jsonl("chaos_recovery"),
        "trace_json": _trace_json("chaos_recovery"),
        "flight_dir": os.path.join(base, "flight"),
        "value": train["recovery_s"], "unit": "s",
        **train, **serve,
        "resolved_config": _resolved_config(
            {"zero_optimization": {"stage": 1}},
            serving={"n_replicas": 2}),
    }


def row_chaos_recovery():
    """Self-healing chaos row.  The recovery supervisor spawns worker
    subprocesses that force their own virtual CPU meshes, but the
    serving half needs >1 device in-process; smoke mode pins the outer
    process to ONE cpu device, so the smoke variant re-execs itself on a
    virtual 8-device CPU mesh (same pattern as serve_load_multi)."""
    if SMOKE and "--chaos-inner" not in sys.argv:
        import os
        import subprocess

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, __file__, "--row", "chaos_recovery",
               "--smoke", "--chaos-inner"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900, env=env)
        except subprocess.TimeoutExpired:
            return {"metric": "chaos_recovery",
                    "error": "smoke timed out"}
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"metric": "chaos_recovery",
                "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}
    return _chaos_recovery_body()


def _chaos_serve_body():
    """Serving chaos drill (docs/SERVING.md "Fault injection &
    self-healing"): a scripted, seeded FaultPlan — all six fault kinds —
    against a supervised 2 prefill + 2 decode disagg fleet.  The control
    phase records fault-free greedy outputs on the same fleet; the chaos
    phase then demands every request terminate typed (zero hangs), every
    dead/stuck replica quarantined + respawned within the heal deadline,
    the decode tier collapse into degraded homogeneous routing and
    restore after healing, and every chaos-phase completion bit-identical
    to its control twin.  Frozen keys linted by tools/telemetry_check.py
    against docs/SERVING.md."""
    import tempfile

    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.resilience.chaos import FaultPlan, attach_chaos
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.serving import (DisaggRouter, FleetSampler,
                                       FleetSupervisor, ReplicaSet,
                                       RequestCancelled, RequestShed,
                                       SamplingParams, ServingError)
    from deepspeed_tpu.telemetry import Telemetry

    model = get_model_config("llama-tiny", num_layers=2)
    if SMOKE:
        n_req, new, rate, wait_s = 24, 8, 40.0, 240.0
        eng_cfg = {"dtype": "float32",
                   "memory_config": {"num_blocks": 96, "block_size": 4},
                   "max_context": 64}
    else:
        n_req, new, rate, wait_s = 64, 16, 48.0, 600.0
        eng_cfg = {"memory_config": {"num_blocks": 512}}
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, model.vocab_size, size=12).tolist()
               for _ in range(n_req)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
    # every 6th request is below-floor priority: the shed_low_priority
    # rung (if pressure climbs that far) must take exactly this class
    prios = [-1 if i % 6 == 5 else 0 for i in range(n_req)]

    base = tempfile.mkdtemp(prefix="dstpu_chaos_serve_")
    flight_dir = os.path.join(base, "flight")
    tel = Telemetry(TelemetryConfig(
        enabled=True, jsonl_path=_telemetry_jsonl("chaos_serve"),
        run_id=_run_id(),
        tracing={"enabled": True,
                 "trace_path": _trace_json("chaos_serve")}))

    rs = ReplicaSet.build(model, 4, eng_cfg,
                          {"admission": {"max_queue_size": 32}}, seed=0,
                          disagg={"enabled": True, "prefill_replicas": 2,
                                  "decode_replicas": 2})
    router = DisaggRouter(rs, telemetry=tel).start()

    # control phase: fault-free greedy outputs through the SAME disagg
    # path (this also pays every compile before the chaos clock starts);
    # respawned replicas rebuild from the same seed, so chaos-phase
    # completions must reproduce these bit-for-bit
    control = router.generate(prompts, max_new_tokens=new)
    assert all(len(o) == new for o in control), "control run incomplete"

    sampler = FleetSampler(rs, router=router, slo=_slo_spec(),
                           cadence_s=0.25,
                           jsonl_path=_fleet_jsonl("chaos_serve"),
                           telemetry=tel).start()
    sup = FleetSupervisor(
        rs, router=router, sampler=sampler, telemetry=tel,
        flight_dir=flight_dir,
        config={"cadence_s": 0.2, "suspect_ticks": 2,
                "stuck_after_s": 1.0, "straggler_factor": 8.0,
                "heal_deadline_s": 60.0 if SMOKE else 30.0,
                "max_heals": 6,
                "brownout": {"enter": 0.5, "exit": 0.2, "dwell_s": 0.3,
                             "priority_floor": 0}}).start()

    # the scripted fault plan — all six kinds, offsets from arm time.
    # Both decode replicas (r2, r3) crash ~together so the decode pool
    # empties while healing is still in flight: the supervisor must
    # collapse the tiers, heal, then restore them.
    plan = FaultPlan([
        {"kind": "slow_replica", "target": "r0", "at": 0.1,
         "duration_s": 3.0, "params": {"delay_ms": 30.0}},
        {"kind": "handoff_fail", "target": "r2", "at": 0.2},
        {"kind": "admission_storm", "target": "r0", "at": 0.4,
         "params": {"burst": 4, "priority": -100, "max_new_tokens": 4}},
        {"kind": "cancel_storm", "target": "r2", "at": 0.5,
         "params": {"count": 2}},
        {"kind": "replica_hang", "target": "r1", "at": 0.8},
        {"kind": "replica_crash", "target": "r2", "at": 0.9},
        {"kind": "replica_crash", "target": "r3", "at": 0.95},
    ], seed=7)
    injectors = attach_chaos(rs, plan, router=router)

    streams, shed_at_submit = {}, 0
    t0 = time.perf_counter()
    for i in range(n_req):
        lag = arrivals[i] - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        try:
            streams[i] = router.submit(
                prompts[i], SamplingParams(max_new_tokens=new),
                priority=prios[i])
        except RequestShed:
            shed_at_submit += 1

    completed, shed, cancelled, failed, hung = 0, shed_at_submit, 0, 0, 0
    outs = {}
    for i, s in streams.items():
        try:
            outs[i] = s.result(timeout=wait_s)
            completed += 1
        except RequestShed:
            shed += 1
        except RequestCancelled:
            cancelled += 1
        except TimeoutError:
            hung += 1
        except ServingError:
            failed += 1

    # settle: every casualty healed, tiers restored, before reading out
    deadline = time.monotonic() + wait_s
    while time.monotonic() < deadline:
        snap = sup.snapshot()
        if (not snap["failed"] and not router.collapsed
                and all(st in ("healthy", "respawned")
                        for st in snap["states"].values())):
            break
        time.sleep(0.25)
    sup.stop()
    sup.check()                    # heal budget must NOT have blown
    snap = sup.snapshot()
    heals = [e for e in sup.events if e.get("state") == "respawned"]
    brownouts = [e for e in sup.events if e.get("state") == "brownout"]
    sampler.stop()
    sampler.sample_once()
    hist = sampler.history()
    router.stop()
    _reset_topology()
    tel.close()

    kinds = set()
    for inj in injectors.values():
        kinds |= inj.fired_kinds
    faults_injected = sum(inj.injected for inj in injectors.values())
    mismatch = [i for i in outs if outs[i] != control[i]]
    curve = {}
    for row in hist:
        curve[row["tick"]] = (curve.get(row["tick"], 0)
                              + int(row["slo_violation"]))
    heal_s = [e["heal_s"] for e in heals]
    from deepspeed_tpu.serving.admission import brownout_index

    # the acceptance gates — each failure names the evidence
    assert hung == 0, f"{hung} requests never terminated"
    assert len(kinds) >= 4, f"only {sorted(kinds)} fired"
    assert snap["heals"] >= 3 and len(heals) >= 3, (snap, len(heals))
    assert all(st in ("healthy", "respawned")
               for st in snap["states"].values()), snap["states"]
    assert snap["collapses"] >= 1 and snap["restores"] >= 1, snap
    assert not mismatch, f"chaos outputs diverged on requests {mismatch}"
    assert completed >= n_req // 2, (completed, n_req)
    return {
        "metric": "chaos_serve_completed",
        "telemetry_jsonl": _telemetry_jsonl("chaos_serve"),
        "trace_json": _trace_json("chaos_serve"),
        "fleet_jsonl": _fleet_jsonl("chaos_serve"),
        "flight_dir": flight_dir,
        "value": completed, "unit": "requests",
        "vs_baseline": round(completed / n_req, 3),
        "faults_injected": faults_injected,
        "fault_kinds": sorted(kinds),
        "completed_chaos": completed,
        "shed_chaos": shed,
        "cancelled_chaos": cancelled,
        "failed_chaos": failed,
        "heals": snap["heals"],
        "time_to_heal_s": round(max(heal_s), 3) if heal_s else -1.0,
        "collapses": snap["collapses"],
        "restores": snap["restores"],
        "bit_identical": int(not mismatch),
        "brownout_peak": max([brownout_index(e["level"])
                              for e in brownouts] or [0]),
        "slo_violations_curve": [curve[t] for t in sorted(curve)],
        "resolved_config": _resolved_config(
            {}, serving={"n_replicas": 4,
                         "disagg": {"enabled": True,
                                    "prefill_replicas": 2,
                                    "decode_replicas": 2},
                         "supervisor": {"max_heals": 6,
                                        "brownout": True}}),
    }


def row_chaos_serve():
    """Serving chaos-drill row.  The disagg fleet needs 8 devices; smoke
    mode pins ONE cpu device, so the smoke variant re-execs itself on a
    virtual 8-device CPU mesh (same pattern as serve_disagg)."""
    if SMOKE and "--chaos-serve-inner" not in sys.argv:
        import os
        import subprocess

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, __file__, "--row", "chaos_serve",
               "--smoke", "--chaos-serve-inner"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900, env=env)
        except subprocess.TimeoutExpired:
            return {"metric": "chaos_serve", "error": "smoke timed out"}
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return {"metric": "chaos_serve",
                "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}
    return _chaos_serve_body()


def row_plan_validate():
    """Planner regression row (docs/PLANNER.md "Regression gate"): the
    plan compiler re-derives every pinned known-good bench config from
    first principles — for each audit row, compile the query mirroring
    the row's experiment space and report the 1-based rank of the row's
    pinned config; then propose the 6.7B offload ladder rung
    sight-unseen on a 1-chip host+NVMe fleet.  Pure analytic CPU work:
    identical in smoke and on-chip runs.  Keys frozen in
    tools/telemetry_check.py."""
    from deepspeed_tpu.planner import (FleetSpec, ModelSpec, compile_plan,
                                       plan_rank_of)
    from deepspeed_tpu.planner.audit import PLAN_AUDIT_ROWS, plan_for_row

    ranks = {}
    for name in PLAN_AUDIT_ROWS:
        plan = plan_for_row(name)
        ranks[name] = plan_rank_of(plan, PINNED_ROW_CONFIGS[name])
    # sight-unseen: the chunked 6.7B rung on a fleet the planner has
    # never benched — 1 chip, 64 GiB host, NVMe (the r16 ladder box)
    model = ModelSpec.from_name("gpt2-6.7b", seq_len=512)
    fleet = FleetSpec(chips=1, hbm_bytes=16 << 30, host_bytes=64 << 30,
                      nvme=True)
    plan67 = compile_plan(model, fleet, max_micro_batch=4)
    ranks["gpt2_6_7b_chunked"] = plan_rank_of(
        plan67, PINNED_ROW_CONFIGS["gpt2_6_7b_chunked"])
    hits = sum(1 for r in ranks.values() if r is not None and r <= 3)
    return {
        "metric": "plan_validate_known_good_top3",
        "value": hits, "unit": "rows",
        "vs_baseline": round(hits / len(ranks), 3),
        "known_good_ranks": ranks,
        "proposed_6_7b": (plan67.ranked[0].candidate
                          if plan67.ranked else None),
        "pruned_6_7b": len(plan67.pruned),
        "evidence_keys_ok": _plan_evidence_ok(plan67),
    }


def _plan_evidence_ok(plan) -> bool:
    from deepspeed_tpu.planner import PLAN_EVIDENCE_KEYS

    want = tuple(sorted(PLAN_EVIDENCE_KEYS))
    return bool(plan.ranked) and all(
        tuple(sorted(e.evidence)) == want for e in plan.ranked)


_ROWS = {
    "gpt2_350m_autosched": row_gpt2_350m_autosched,
    "gpt2_350m_commquant": row_gpt2_350m_commquant,
    "llama8b_class_zero3": row_llama8b_class_zero3,
    "longseq_flash": row_longseq_flash,
    "longseq_llama": row_longseq_llama,
    "longseq_ring": row_longseq_ring,
    "peak_params": row_peak_params,
    "v2_decode": row_v2_decode,
    "serve_load": row_serve_load,
    "serve_load_multi": row_serve_load_multi,
    "serve_disagg": row_serve_disagg,
    "chaos_recovery": row_chaos_recovery,
    "chaos_serve": row_chaos_serve,
    "plan_validate": row_plan_validate,
    "gpt2_350m": row_gpt2_350m,
}


def _run_row(name: str) -> dict:
    """Run one row in THIS process; a failure becomes an ``error`` row
    (and a non-zero exit in main)."""
    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    try:
        return _write_row_manifest(name, _ROWS[name]())
    except Exception as e:
        return {"metric": name, "error": str(e)[:250], "run_id": _run_id()}


def _run_row_subprocess(name: str, timeout_s: float = 900.0) -> dict:
    """Run one row in a fresh interpreter.

    Isolation is load-bearing, not hygiene: rows materialize multi-GB
    engines, and a row that dies mid-compile (or mid-step) can leave its
    HBM buffers live in this process, cascading RESOURCE_EXHAUSTED into
    every later row (observed r04: one failing row zeroed the whole
    report). A subprocess exit frees the chip unconditionally."""
    import subprocess

    cmd = [sys.executable, __file__, "--row", name]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"metric": name, "error": f"row timed out after {timeout_s}s",
                "run_id": _run_id()}
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return {"metric": name, "run_id": _run_id(),
            "error": ("no result line; " + " | ".join(tail[-3:]))[:300]}


# peak_params walks the ladder serially; the NVMe rungs alone can spend
# 1500+1200+900 s before the cpu rungs run, so the row budget must cover
# a failing-descent worst case
_ROW_TIMEOUTS = {"peak_params": 5400.0}


def main() -> None:
    if "--peak-entry" in sys.argv:
        from deepspeed_tpu.utils.platform import setup_compile_cache

        setup_compile_cache()
        idx = int(sys.argv[sys.argv.index("--peak-entry") + 1])
        print(json.dumps(_peak_entry(idx)), flush=True)
        return
    if "--row" in sys.argv:
        name = sys.argv[sys.argv.index("--row") + 1]
        # inherit the parent's run id (env) or mint one for direct
        # invocations — smoke re-exec inners must share the outer's id
        os.environ.setdefault("DSTPU_RUN_ID", _mint_run_id(name))
        r = _run_row(name)
        print(json.dumps(r), flush=True)
        sys.exit(1 if "error" in r else 0)
    rows = []
    for name in ("llama8b_class_zero3", "longseq_flash", "longseq_llama",
                 "longseq_ring", "gpt2_350m_commquant",
                 "gpt2_350m_autosched", "peak_params",
                 "v2_decode", "serve_load", "serve_load_multi",
                 "serve_disagg", "chaos_recovery", "chaos_serve",
                 "plan_validate"):
        # one run id per row, minted HERE so subprocess rows inherit it
        # through the environment and every artifact carries the same id
        os.environ["DSTPU_RUN_ID"] = _mint_run_id(name)
        if SMOKE:
            r = _run_row(name)
        else:
            r = _run_row_subprocess(name, _ROW_TIMEOUTS.get(name, 900.0))
        rows.append(r)
        print(json.dumps(r), flush=True)
    os.environ["DSTPU_RUN_ID"] = _mint_run_id("gpt2_350m")
    primary = (_run_row("gpt2_350m") if SMOKE
               else _run_row_subprocess("gpt2_350m"))
    if "error" in primary:
        # the LAST line is what the driver records — it must be the
        # primary metric (or its explicit failure), never a stray
        # secondary row
        primary = {"metric":
                   "gpt2_350m_zero1_train_tokens_per_sec_per_chip",
                   "value": 0.0, "unit": "tokens/s", "vs_baseline": 0.0,
                   "error": primary["error"]}
    primary["rows"] = rows
    print(json.dumps(primary), flush=True)
    failed = [r["metric"] for r in rows + [primary] if "error" in r]
    if failed:
        # an error row is a failed run, not a result
        sys.exit(f"bench: {len(failed)} row(s) errored: {', '.join(failed)}")


if __name__ == "__main__":
    main()
