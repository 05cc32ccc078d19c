"""Unified telemetry layer (deepspeed_tpu/telemetry/): StepRecord JSONL,
shared registry primitives, Prometheus export, auto-capture overlap
reports, and the satellite fixes that feed them (timer reset semantics,
comms volume clamp, flops-profiler degradation)."""

import json
import os
import time
import types

import numpy as np
import pytest

from deepspeed_tpu.telemetry import (EXPORT_TAGS, MetricsRegistry,
                                     StepRecord, Telemetry,
                                     build_capture_report,
                                     events_from_record, read_jsonl,
                                     render_prometheus)
from deepspeed_tpu.telemetry.registry import Counter, Gauge, Histogram


# ----------------------------------------------------------------------
# registry primitives
# ----------------------------------------------------------------------
def test_registry_get_or_create_shares_instances():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "help")
    c2 = reg.counter("x_total")
    assert c1 is c2
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        c1.inc(-1)


def test_histogram_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds")
    xs = list(range(1, 101))
    for x in xs:
        h.observe(float(x))
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["p50"] == pytest.approx(np.percentile(xs, 50))
    assert snap["p95"] == pytest.approx(np.percentile(xs, 95))
    assert snap["p99"] == pytest.approx(np.percentile(xs, 99))
    assert snap["mean"] == pytest.approx(np.mean(xs))
    # empty histogram snapshots to zeros, not NaN/crash
    empty = reg.histogram("empty_seconds").snapshot()
    assert empty == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                     "mean": 0.0, "count": 0}


def test_histogram_window_bounds_memory_but_count_is_lifetime():
    h = Histogram("h", window=4)
    for x in (1, 2, 3, 4, 100, 100, 100, 100):
        h.observe(x)
    snap = h.snapshot()
    assert snap["count"] == 8          # lifetime
    assert snap["p50"] == 100          # window holds only the last 4
    assert h.lifetime() == (8, 410.0)


def test_histogram_time_window_idle_p95_decays(monkeypatch):
    """max_age_s > 0: an idle histogram's percentiles fall back to zero
    once the last burst ages out — count stays lifetime (regression for
    the fleet sampler: an idle tier must not hold its last-burst p95)."""
    import deepspeed_tpu.telemetry.registry as reg_mod

    clock = {"t": 1000.0}
    monkeypatch.setattr(reg_mod.time, "monotonic", lambda: clock["t"])
    h = Histogram("h", max_age_s=30.0)
    for x in (5.0, 7.0, 9.0):
        h.observe(x)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["p95"] > 0.0
    clock["t"] += 31.0                       # burst ages out
    snap = h.snapshot()
    assert snap == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                    "mean": 0.0, "count": 3}
    h.observe(2.0)                           # fresh sample re-populates
    assert h.snapshot()["p95"] == 2.0
    assert h.lifetime() == (4, 23.0)
    # default (max_age_s=0) keeps the historical lifetime behavior
    h0 = Histogram("h0")
    h0.observe(5.0)
    clock["t"] += 1e6
    assert h0.snapshot()["p95"] == 5.0


def test_prometheus_rendering():
    reg = MetricsRegistry()
    reg.counter("steps_total", "steps").inc(3)
    reg.gauge("mfu").set(0.42)
    h = reg.histogram("lat_seconds")
    h.observe(1.0)
    h.observe(3.0)
    text = render_prometheus(reg)
    assert "# TYPE steps_total counter" in text
    assert "steps_total 3" in text
    assert "# TYPE mfu gauge" in text
    assert "mfu 0.42" in text
    assert "# TYPE lat_seconds summary" in text
    assert 'lat_seconds{quantile="0.5"}' in text
    assert "lat_seconds_count 2" in text
    assert "lat_seconds_sum 4" in text


# ----------------------------------------------------------------------
# StepRecord
# ----------------------------------------------------------------------
def test_step_record_derived_fields_and_sorted_json():
    rec = StepRecord(step=5, wall_time_s=0.5, tokens=1000,
                     flops_per_step=1e9, peak_flops_per_sec=1e12)
    assert rec.tokens_per_sec == pytest.approx(2000.0)
    assert rec.achieved_flops_per_sec == pytest.approx(2e9)
    assert 0.0 < rec.mfu <= 1.0
    d = json.loads(rec.to_json())
    assert d["schema"] == 3
    assert list(d.keys()) == sorted(d.keys())
    # mfu clamps at 1.0 even when "achieved" exceeds the peak estimate
    hot = StepRecord(step=1, wall_time_s=0.1, tokens=1,
                     flops_per_step=1e13, peak_flops_per_sec=1e12)
    assert hot.mfu == 1.0


def test_events_from_record_covers_export_tags():
    rec = StepRecord(step=2, wall_time_s=0.1, tokens=10,
                     flops_per_step=1e6, peak_flops_per_sec=1e12,
                     loss=1.5, grad_norm=0.3, lr=1e-3, loss_scale=1.0,
                     hbm={"device_0": {"bytes_in_use": 10,
                                       "peak_bytes_in_use": 20}},
                     comm={"all_reduce": {"count": 2, "bytes": 256}})
    events = events_from_record(rec)
    tags = {t for t, _, _ in events}
    assert tags == set(EXPORT_TAGS)
    by_tag = {t: v for t, v, _ in events}
    assert by_tag["telemetry/hbm_bytes_in_use"] == 10
    assert by_tag["telemetry/comm_bytes_total"] == 256
    assert all(s == 2 for _, _, s in events)


def test_telemetry_hub_jsonl_and_serving_record(tmp_path):
    from deepspeed_tpu.runtime.config import TelemetryConfig

    path = str(tmp_path / "steps.jsonl")
    tel = Telemetry(TelemetryConfig(enabled=True, jsonl_path=path))
    tel.set_flops(1e9, "analytic")
    tel.record_train_step(step=1, wall_time_s=0.25, tokens=512, loss=2.0,
                          skipped=False)
    tel.record_train_step(step=2, wall_time_s=0.25, tokens=512, loss=2.0,
                          skipped=True)
    tel.record_serving_step(3, {"tokens_out": 7, "tokens_per_sec": 14.0,
                                "ttft": {"p50": 0.1}})
    tel.close()
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["train", "train", "serving"]
    assert recs[0]["goodput"] == 1.0
    assert recs[1]["goodput"] == 0.5 and recs[1]["skipped"] is True
    assert recs[2]["serving"]["ttft_p50"] == 0.1
    assert recs[2]["tokens"] == 7
    # registry reflects the same run
    assert tel.registry.get("telemetry_steps_total").value == 2
    assert tel.registry.get("telemetry_skipped_steps_total").value == 1


def test_should_record_interval_with_capture_override(tmp_path):
    from deepspeed_tpu.runtime.config import TelemetryConfig

    tel = Telemetry(TelemetryConfig(enabled=True, interval_steps=3))
    assert [s for s in range(1, 8) if tel.should_record(s)] == [3, 6]
    # a regression-triggered capture needs every step's wall time, so it
    # overrides the thinning
    tel2 = Telemetry(TelemetryConfig(
        enabled=True, interval_steps=5,
        capture={"enabled": True, "regression_factor": 2.0,
                 "output_dir": str(tmp_path)}))
    assert all(tel2.should_record(s) for s in range(1, 8))


def test_capture_override_ends_with_exhausted_budget(tmp_path):
    """Once the capture budget is spent, the regression override must
    stop defeating interval thinning (every later step would otherwise
    pay the hard sync + export forever)."""
    from deepspeed_tpu.runtime.config import TelemetryConfig

    tel = Telemetry(TelemetryConfig(
        enabled=True, interval_steps=4,
        capture={"enabled": True, "regression_factor": 2.0,
                 "budget": 1, "output_dir": str(tmp_path)}))
    assert tel.should_record(1)           # budget left → every step
    assert not tel.is_full_record_step(1)  # ...but observe-only
    assert tel.is_full_record_step(4)
    tel.capture.budget_left = 0
    assert not tel.should_record(1)       # thinning applies again
    assert tel.should_record(4)


def test_engine_comm_delta_excludes_prior_traffic():
    """StepRecord.comm must be the delta vs the engine's construction
    baseline, not the process-global cumulative totals."""
    from deepspeed_tpu.utils.comms_logging import get_comms_logger

    cl = get_comms_logger()
    was_enabled = cl.enabled
    cl.enabled = True
    try:
        cl.record("all_reduce", np.zeros((4,), np.float32), "data")
        # fake just the attributes _comm_delta reads
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        eng = types.SimpleNamespace(_comms_baseline=cl.totals())
        assert DeepSpeedEngine._comm_delta(eng) == {}
        cl.record("all_reduce", np.zeros((8,), np.float32), "data")
        delta = DeepSpeedEngine._comm_delta(eng)
        assert delta == {"all_reduce": {"count": 1, "bytes": 32}}
    finally:
        cl.enabled = was_enabled


def test_stale_record_not_cross_checked_against_capture(tmp_path):
    """With interval-thinned telemetry the last record can predate the
    capture window — the report must omit the MFU cross-check rather
    than pair the trace with the wrong step."""
    from deepspeed_tpu.runtime.config import TelemetryCaptureConfig
    from deepspeed_tpu.telemetry.capture import AutoCapture

    cap = AutoCapture(TelemetryCaptureConfig(
        enabled=True, num_steps=1, output_dir=str(tmp_path)),
        telemetry=types.SimpleNamespace(last_record=StepRecord(step=10)))
    cap._armed_at = 15
    path = cap._write_report(str(tmp_path / "empty"))
    with open(path) as f:
        rep = json.load(f)
    assert "mfu_cross_check" not in rep
    assert "no StepRecord inside the capture window" in rep["note"]
    # an in-window record IS cross-checked, stamped with its step
    cap2 = AutoCapture(TelemetryCaptureConfig(
        enabled=True, num_steps=1, output_dir=str(tmp_path)),
        telemetry=types.SimpleNamespace(last_record=StepRecord(step=15)))
    cap2._armed_at = 15
    with open(cap2._write_report(str(tmp_path / "empty2"))) as f:
        rep2 = json.load(f)
    assert rep2["mfu_cross_check"]["record_step"] == 15


def test_record_train_step_feeds_capture_regression_window(tmp_path):
    """The hub is the single feed point for the trigger's trailing
    step-time window — a regression seen only via record_train_step
    must arm it (the engine passes no wall time to on_step_end)."""
    from deepspeed_tpu.runtime.config import TelemetryConfig

    tel = Telemetry(TelemetryConfig(
        enabled=True,
        capture={"enabled": True, "regression_factor": 2.0,
                 "budget": 1, "output_dir": str(tmp_path)}))
    for i in range(12):
        tel.record_train_step(step=i + 1, wall_time_s=0.1, tokens=1)
    assert not tel.capture._regressed()
    tel.record_train_step(step=13, wall_time_s=5.0, tokens=1)
    tel.record_train_step(step=14, wall_time_s=5.0, tokens=1)
    assert tel.capture._regressed()


def test_serving_metrics_import_stays_jax_free():
    """PR-2 invariant: serving/ itself uses no jax (the parent package
    __init__ pulls jax regardless — the invariant is about the serving
    and telemetry module code, so the jax-0.4.37 compat surface stays
    moot there).  The shared-registry refactor must therefore never load
    telemetry.capture (the only jax-tainted telemetry module; it imports
    utils.trace) as a side effect of importing serving metrics."""
    import subprocess
    import sys as _sys

    code = (
        "import deepspeed_tpu.serving.metrics, sys; "
        "assert 'deepspeed_tpu.telemetry.capture' not in sys.modules; "
        "assert 'deepspeed_tpu.utils.trace' not in sys.modules; "
        "src = open(deepspeed_tpu.serving.metrics.__file__).read(); "
        "assert 'import jax' not in src; print('ok')")
    proc = subprocess.run([_sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout


# ----------------------------------------------------------------------
# satellite: Timer.elapsed(reset=True) on a running timer
# ----------------------------------------------------------------------
def test_timer_elapsed_reset_preserves_running_interval():
    from deepspeed_tpu.utils.timer import Timer

    t = Timer("t")
    t.start()
    time.sleep(0.02)
    first = t.elapsed(reset=True)
    assert first >= 0.015
    # regression: reset used to clear `started`, killing the in-flight
    # interval — the timer must still be running with a rebased start
    assert t.started
    time.sleep(0.02)
    t.stop()
    second = t.elapsed(reset=True)
    assert second >= 0.015
    # the pre-reset interval must NOT be double counted into the second
    assert second < first + 0.25


def test_timer_elapsed_reset_idle_still_clears():
    from deepspeed_tpu.utils.timer import Timer

    t = Timer("t")
    t.start()
    time.sleep(0.01)
    t.stop()
    assert t.elapsed(reset=True) > 0
    assert not t.started
    assert t.elapsed(reset=False) == 0.0


# ----------------------------------------------------------------------
# satellite: comms volume clamp + totals
# ----------------------------------------------------------------------
def test_calc_bw_log_single_device_clamps():
    from deepspeed_tpu.utils.comms_logging import calc_bw_log

    # n=1: ring factor 2(n-1)/n collapses to 0 — clamped to bus == alg
    r = calc_bw_log("all_reduce", 1 << 20, 1e-3, 1)
    assert r["algbw_gbps"] > 0
    assert r["busbw_gbps"] == pytest.approx(r["algbw_gbps"])
    # degenerate n<=0 must not divide by zero / go negative
    r0 = calc_bw_log("all_gather", 1 << 20, 1e-3, 0)
    assert r0["busbw_gbps"] == pytest.approx(r0["algbw_gbps"])
    # the multi-device formulas are untouched
    r4 = calc_bw_log("all_reduce", 1 << 20, 1e-3, 4)
    assert r4["busbw_gbps"] == pytest.approx(r4["algbw_gbps"] * 1.5)


def test_comms_logger_totals_per_op():
    from deepspeed_tpu.utils.comms_logging import CommsLogger

    cl = CommsLogger(enabled=True)
    a = np.zeros((4, 4), np.float32)     # 64 B
    b = np.zeros((8,), np.float32)       # 32 B
    cl.record("all_reduce", a, "data")
    cl.record("all_reduce", a, "data")
    cl.record("all_reduce", b, "data")
    cl.record("all_gather", b, "data")
    tot = cl.totals()
    assert tot["all_reduce"] == {"count": 3, "bytes": 160}
    assert tot["all_gather"] == {"count": 1, "bytes": 32}
    cl.log_summary()                      # TOTAL rows must not crash
    cl.reset()
    assert cl.totals() == {}


# ----------------------------------------------------------------------
# satellite: flops profiler degradation + analytic formula
# ----------------------------------------------------------------------
class _FakeCompiled:
    def __init__(self, ca, mem="raise"):
        self._ca, self._mem = ca, mem

    def cost_analysis(self):
        return self._ca

    def memory_analysis(self):
        if self._mem == "raise":
            raise RuntimeError("backend has no memory analysis")
        return self._mem


class _FakeJit:
    def __init__(self, compiled):
        self._compiled = compiled

    def lower(self, *a, **kw):
        return types.SimpleNamespace(compile=lambda: self._compiled)


def test_profile_compiled_degrades_gracefully():
    from deepspeed_tpu.profiling.flops_profiler import profile_compiled

    # list-shaped cost_analysis (one dict per computation)
    out = profile_compiled(_FakeJit(_FakeCompiled([{"flops": 5.0}])))
    assert out == {"flops": 5.0}
    # empty list / missing keys / raising memory_analysis → empty result
    assert profile_compiled(_FakeJit(_FakeCompiled([]))) == {}
    assert profile_compiled(_FakeJit(_FakeCompiled({}))) == {}
    out = profile_compiled(_FakeJit(_FakeCompiled(
        {"bytes accessed": 3.0}, mem=None)))
    assert out == {"bytes_accessed": 3.0}
    # memory_analysis present → summed peak
    mem = types.SimpleNamespace(temp_size_in_bytes=10,
                                argument_size_in_bytes=20,
                                output_size_in_bytes=30)
    out = profile_compiled(_FakeJit(_FakeCompiled({"flops": 1.0},
                                                  mem=mem)))
    assert out["peak_memory_bytes"] == 60.0


def test_analytic_model_profile_hand_computed():
    from deepspeed_tpu.profiling.flops_profiler import get_model_profile

    cfg = types.SimpleNamespace(
        hidden_size=4, num_heads=2, kv_heads=2, dim_per_head=2,
        intermediate_size=8, activation="gelu", num_layers=1,
        vocab_size=10, norm="layernorm", num_experts=0)
    prof = get_model_profile(cfg, batch_size=1, seq_len=3,
                             include_backward=False)
    # hand computation: qkv 288 + scores 144 + attn_out 96 + mlp 384
    # = 912/layer; logits 240 → fwd 1152
    assert prof["fwd_flops"] == 1152.0
    assert prof["breakdown_per_layer"]["attention_qkv"] == 288.0
    assert prof["breakdown_per_layer"]["mlp"] == 384.0
    assert prof["logits_flops"] == 240.0
    full = get_model_profile(cfg, 1, 3, include_backward=True)
    assert full["total_flops_per_step"] == pytest.approx(3 * 1152.0)
    recomp = get_model_profile(cfg, 1, 3, include_backward=True,
                               recompute_fwd_factor=1.0)
    assert recomp["total_flops_per_step"] == pytest.approx(4 * 1152.0)


# ----------------------------------------------------------------------
# capture reports
# ----------------------------------------------------------------------
def test_capture_report_empty_dir(tmp_path):
    rep = build_capture_report(str(tmp_path))
    assert rep["overlap_fraction"] == 0.0
    assert "no xplane files" in rep["note"]


def test_capture_report_synthetic_device_plane(tmp_path):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add(name="/device:TPU:0")
    for mid, n in {1: "fusion.42", 2: "all-reduce.7", 3: "dot.3"}.items():
        plane.event_metadata[mid].name = n
    line = plane.lines.add(timestamp_ns=0)
    ms = 10 ** 9  # ps per ms — report times must survive ms rounding
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=1 * ms)
    line.events.add(metadata_id=2, offset_ps=ms // 2, duration_ps=1 * ms)
    line.events.add(metadata_id=3, offset_ps=2 * ms, duration_ps=ms // 2)
    (tmp_path / "t.xplane.pb").write_bytes(xs.SerializeToString())

    rec = StepRecord(step=3, wall_time_s=0.1, tokens=10,
                     flops_per_step=1e6, peak_flops_per_sec=1e12,
                     flops_source="analytic")
    rep = build_capture_report(str(tmp_path), step_record=rec)
    assert rep["overlap_fraction"] == 0.5
    names = [o["name"] for o in rep["top_ops"]]
    assert "all-reduce.7" in names and "fusion.42" in names
    cc = rep["mfu_cross_check"]
    assert cc["analytic_mfu"] == rec.mfu
    assert cc["capture_collective_ms"] > 0


def test_autocapture_regression_trigger_and_budget(tmp_path):
    from deepspeed_tpu.runtime.config import TelemetryCaptureConfig
    from deepspeed_tpu.telemetry.capture import AutoCapture

    cfg = TelemetryCaptureConfig(enabled=True, regression_factor=2.0,
                                 budget=1, window=16,
                                 output_dir=str(tmp_path))
    cap = AutoCapture(cfg)
    for _ in range(12):
        cap.observe_step_time(0.1)
    assert not cap._regressed()          # flat distribution
    cap.observe_step_time(1.0)           # p95 now 10× the median
    cap.observe_step_time(1.0)
    assert cap._regressed()
    # below the minimum sample count the trigger must stay quiet
    cold = AutoCapture(cfg)
    cold.observe_step_time(9.0)
    assert not cold._regressed()
    # factor 0 disables the trigger entirely
    off = AutoCapture(TelemetryCaptureConfig(
        enabled=True, regression_factor=0.0, output_dir=str(tmp_path)))
    for _ in range(20):
        off.observe_step_time(0.1)
    off.observe_step_time(50.0)
    assert not off._regressed()


# ----------------------------------------------------------------------
# serving metrics now run on the shared registry
# ----------------------------------------------------------------------
def test_serving_metrics_use_shared_registry_histograms():
    import deepspeed_tpu.serving.metrics as sm

    # the private window implementation is gone
    assert not hasattr(sm, "_percentiles")
    reg = MetricsRegistry()
    m = sm.ServingMetrics(registry=reg)
    for v in (0.1, 0.2, 0.3):
        m.record_first_token(v)
    m.record_admit(0.05)
    m.record_tokens(5)
    m.record_finish("completed", 3, first_token_at=1.0, finished_at=1.4)
    # the registry object IS the serving histogram
    h = reg.get("serving_ttft_seconds")
    assert isinstance(h, Histogram)
    snap = m.snapshot()
    assert snap["ttft"] == h.snapshot()
    assert snap["ttft"]["count"] == 3
    assert snap["ttft"]["p50"] == pytest.approx(0.2)
    assert snap["tpot"]["p50"] == pytest.approx(0.2)  # (1.4-1.0)/(3-1)
    assert snap["completed"] == 1 and snap["tokens_out"] == 5
    assert reg.get("serving_completed_total").value == 1
    # monitor-event flattening unchanged
    tags = {t for t, _, _ in m.events(7)}
    assert {"serving/ttft_p50", "serving/tpot_p95",
            "serving/tokens_out"} <= tags


def test_serving_metrics_counters_gauges():
    from deepspeed_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.record_submit()
    m.record_reject()
    m.record_preemption()
    m.record_step()
    m.set_gauges(queue_depth=3, active=2, kv_utilization=0.5)
    assert (m.submitted, m.rejected, m.preemptions, m.steps) == (1, 1, 1, 1)
    assert (m.queue_depth, m.active_requests) == (3, 2)
    assert m.kv_utilization == 0.5
    with pytest.raises(ValueError):
        m.record_finish("exploded", 1, None, 0.0)


# ----------------------------------------------------------------------
# the telemetry_check lint runs as a normal tier-1 test
# ----------------------------------------------------------------------
@pytest.fixture
def telemetry_check():
    from tools import telemetry_check

    return telemetry_check


def test_telemetry_check_lint_passes(telemetry_check):
    assert telemetry_check.run_all() == []


# (check, the frozen list that gains a name no module has)
_FROZEN_LIST_OF_CHECK = [
    ("check_schema", "EXPECTED_RECORD_KEYS"),
    ("check_span_names", "EXPECTED_SPAN_NAMES"),
    ("check_quant_comm", "EXPECTED_QUANT_COMM_OPS"),
    ("check_router_serving", "EXPECTED_REPLICA_TIERS"),
    ("check_autotuning", "EXPECTED_SCHEDULE_DECISIONS"),
    ("check_graph_audit", "EXPECTED_FINDING_KINDS"),
    ("check_memory_audit", "EXPECTED_MEMORY_CLASSES"),
    ("check_offload", "OFFLOAD_CONFIG_KEYS"),
    ("check_recovery", "EXPECTED_RECOVERY_STATES"),
    ("check_planner", "EXPECTED_LINK_CLASSES"),
    ("check_fleet", "EXPECTED_TIER_SNAPSHOT_KEYS"),
    ("check_chaos_fleet", "EXPECTED_FAULT_KINDS"),
]


@pytest.mark.parametrize("check,frozen", _FROZEN_LIST_OF_CHECK,
                         ids=[c for c, _ in _FROZEN_LIST_OF_CHECK])
def test_telemetry_check_trips(telemetry_check, monkeypatch, check, frozen):
    """The lint can fail: a frozen list that drifts from its module is
    reported by the check that owns it, by name."""
    assert getattr(telemetry_check, check)() == []
    planted = "zz_planted_name"
    monkeypatch.setattr(telemetry_check, frozen,
                        list(getattr(telemetry_check, frozen)) + [planted])
    errors = getattr(telemetry_check, check)()
    assert errors and any(planted in e for e in errors), errors


def test_telemetry_check_trips_on_an_undocumented_tag(telemetry_check,
                                                      tmp_path):
    """... and so is a row that goes missing from the document."""
    with open(telemetry_check.DOCS, encoding="utf-8") as f:
        lines = f.read().splitlines(keepends=True)
    kept = [ln for ln in lines if "telemetry/tokens_per_sec" not in ln]
    assert len(kept) < len(lines)
    copy = tmp_path / "OBSERVABILITY.md"
    copy.write_text("".join(kept), encoding="utf-8")
    errors = telemetry_check.check_tags_documented(str(copy))
    assert any("telemetry/tokens_per_sec" in e for e in errors), errors


# ----------------------------------------------------------------------
# acceptance: 3-step CPU train run with telemetry + forced capture
# ----------------------------------------------------------------------
def test_train_run_emits_step_records_and_capture_report(tmp_path):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    jsonl = str(tmp_path / "steps.jsonl")
    prom = str(tmp_path / "metrics.prom")
    cap_dir = str(tmp_path / "captures")
    model = get_model_config("gpt2-tiny")
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
        "telemetry": {
            "enabled": True, "jsonl_path": jsonl,
            "prometheus_path": prom,
            "capture": {"enabled": True, "capture_step": 2,
                        "num_steps": 1, "budget": 1,
                        "output_dir": cap_dir},
        },
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, size=(8, 33), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    for _ in range(3):
        loss = engine.train_batch(batch)
    assert np.isfinite(float(np.asarray(loss)))
    engine.destroy()

    recs = read_jsonl(jsonl)
    assert len(recs) == 3
    for i, r in enumerate(recs):
        assert r["schema"] == 3 and r["kind"] == "train"
        assert r["step"] == i + 1
        assert r["tokens"] == 8 * 32
        assert r["tokens_per_sec"] > 0
        assert 0.0 < r["mfu"] <= 1.0
        assert r["flops_source"] in ("measured", "analytic")
        hbm0 = r["hbm"]["device_0"]
        assert hbm0["bytes_in_use"] > 0
        assert hbm0["peak_bytes_in_use"] >= hbm0["bytes_in_use"] > 0
        assert r["goodput"] == 1.0 and r["skipped"] is False
        assert r["loss"] is not None and np.isfinite(r["loss"])
        # serialized lines are key-sorted (schema lint contract)
        assert list(r.keys()) == sorted(r.keys())

    # the forced capture window produced a persisted overlap report
    report_path = os.path.join(cap_dir, "capture_step2", "report.json")
    assert os.path.exists(report_path), os.listdir(cap_dir)
    with open(report_path) as f:
        rep = json.load(f)
    assert 0.0 <= rep["overlap_fraction"] <= 1.0
    assert rep["armed_at_step"] == 2
    assert "mfu_cross_check" in rep
    assert rep["mfu_cross_check"]["analytic_mfu"] > 0

    # prometheus exposition carries the shared metrics
    with open(prom) as f:
        text = f.read()
    assert "telemetry_steps_total 3" in text
    assert "telemetry_step_time_seconds" in text
