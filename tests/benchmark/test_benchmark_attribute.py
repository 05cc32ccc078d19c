"""``lib.attribute`` and ``readers/spans.py`` on planes, spans and steps
made by hand, on the second trace recorded on a v5e
(``data/span_trace.xplane.pb`` with ``data/span_trace.spans.json``, by
``benchmark/tools/record_span_trace.py``) and through the tiny cells."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

import bench_tiny
from benchmark.lib import attribute as A
from benchmark.lib import device, manifest, trace
from benchmark.lib.peaks import PEAKS
from benchmark.readers import spans as R

DATA = Path(__file__).parent / "data"
MS = 1e6        # ns


# -- one clock ---------------------------------------------------------------
def test_the_reduction_looks_for_the_programs_anchor_name():
    from deepspeed_tpu.utils.trace import CLOCK_ANCHOR

    assert A.ANCHOR == CLOCK_ANCHOR


def test_one_anchor_is_an_offset():
    m = A.anchor_map([(5_000.0, 1_000_000)])
    assert (m.scale, m.drift_ns) == (1.0, 0.0)
    assert m.to_host(1_000_250) == 5_250.0
    assert m.to_monotonic(5_250.0) == 1_000_250
    assert A.anchor_map([]) is None


def test_two_anchors_give_the_drift():
    # the host plane runs 100 ppm fast: 1 s of monotonic is 1.0001 s
    m = A.anchor_map([(0.0, 10_000_000_000), (1_000_100_000.0,
                                              11_000_000_000)])
    assert m.drift_ns == pytest.approx(100_000.0)
    assert m.scale == pytest.approx(1.0001)
    assert m.to_host(10_500_000_000) == pytest.approx(500_050_000.0)
    for t in (10_000_000_000, 10_300_000_000, 11_000_000_000):
        assert m.to_monotonic(m.to_host(t)) == pytest.approx(t)


# runs on the device clock; the host plane is 1.3 ms later.  Each run is
# enqueued 0.05-0.1 ms before it starts and its Done begins 0.1-0.2 ms
# after it ends.
D = 1.3 * MS
RUNS = [(7, 10 * MS, 12 * MS), (8, 12 * MS, 15 * MS), (9, 40 * MS, 41 * MS)]
ENQ = [(10 * MS + D - 0.10 * MS, 7), (12 * MS + D - 0.30 * MS, 8),
       (40 * MS + D - 0.05 * MS, 9)]
DONES = [12 * MS + D + 0.2 * MS, 15 * MS + D + 0.1 * MS,
         41 * MS + D + 0.15 * MS]


def test_offset_bracket_from_the_runtimes_events():
    lo, hi = A.device_offset(RUNS, ENQ, DONES)
    assert lo == pytest.approx(D - 0.05 * MS)       # the tightest enqueue
    assert hi == pytest.approx(D + 0.10 * MS)       # the tightest Done
    assert lo <= D <= hi


def test_offset_bracket_when_the_capture_cut_a_run_off():
    # the capture began while run 6 was on the device: its Done is there,
    # its run and its enqueue are not; and it stopped before run 9's Done
    dones = [9.5 * MS + D] + DONES[:2]
    lo, hi = A.device_offset(RUNS, ENQ, dones)
    assert lo <= D <= hi and hi == pytest.approx(D + 0.10 * MS)
    # run 7's events lost at the start instead
    lo, hi = A.device_offset(RUNS[1:], ENQ[1:], DONES)
    assert lo <= D <= hi and hi == pytest.approx(D + 0.10 * MS)


def test_no_bracket_without_a_pair():
    assert A.device_offset(RUNS, [(1.0, 99)], DONES) is None    # no run id
    assert A.device_offset(RUNS, ENQ, []) is None
    assert A.device_offset([], ENQ, DONES) is None


# -- who was in a gap --------------------------------------------------------
SPANS = [(0, 100, "serve.step"), (10, 40, "v2.h2d"), (40, 70, "v2.dispatch"),
         (100, 130, "serve.deliver")]


def test_a_gap_split_between_two_spans():
    assert A.attribute([(30, 60)], SPANS, 5) == {"v2.h2d": 10,
                                                 "v2.dispatch": 20}


def test_the_innermost_span_takes_the_piece_it_covers():
    # 0..10 and 70..100 lie under serve.step alone: its self time
    assert A.attribute([(0, 130)], SPANS, 5) == {
        "serve.step": 40, "v2.h2d": 30, "v2.dispatch": 30,
        "serve.deliver": 30}


def test_a_gap_under_no_span_keeps_its_old_name():
    assert A.attribute([(125, 150)], SPANS, 5) == {"serve.deliver": 5,
                                                   "": 20}
    assert A.attribute([(125, 150), (200, 210)], SPANS, 5,
                       ["unattributed: a", "unattributed: b"]) == {
        "serve.deliver": 5, "unattributed: a": 20, "unattributed: b": 10}


def test_a_gap_shorter_than_the_clock_error_says_so():
    assert A.attribute([(30, 34), (50, 60)], SPANS, 5) == {
        A.SHORT: 4, "v2.dispatch": 10}


def _ev(name, ts, dur, trace_id="loop", **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "args": dict(args, trace_id=trace_id)}


def test_a_requests_lifetime_spans_are_not_what_the_host_did():
    events = [_ev("serve.step", 0, 50), _ev("v2.dispatch", 5, 10),
              _ev("serve.decode", 0, 5000, trace_id="req1"),
              {"name": "serve.emit", "ph": "i", "ts": 3,
               "args": {"trace_id": "req1"}}]
    assert [e["name"] for e in A.loop_spans(events)] == ["serve.step",
                                                         "v2.dispatch"]
    # without a dispatch span every span counts (a recording tool's)
    assert len(A.loop_spans(events[2:])) == 1


# -- kernels by name ----------------------------------------------------------
def _kernel(name):
    return (f'%{name} = bf16[8,128]{{1,0}} custom-call(bf16[8,128]{{1,0}} '
            f'%x), custom_call_target="tpu_custom_call"')


FUSION = "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop"


def test_pallas_time_by_kernel_name():
    assert A.kernel_name(_kernel("flash_bwd_dq.12")) == "flash_bwd_dq"
    assert A.kernel_name(_kernel("paged_decode")) == "paged_decode"
    # differentiated outside a jax.checkpoint: the wrappers come off
    assert A.kernel_name(_kernel("jvp_flash_fwd_.1")) == "flash_fwd"
    assert A.kernel_name(_kernel("transpose_jvp_flash_bwd_dkv__.3")) \
        == "flash_bwd_dkv"
    # a kernel without a name of its own keeps what the profiler shows
    assert A.kernel_name(_kernel("transpose_jvp___.1")) == "transpose_jvp___"
    assert A.kernel_name(_kernel("checkpoint.18")) == "checkpoint"
    plane = trace.DevicePlane("/device:TPU:0", ops=[
        (_kernel("flash_fwd.1"), 0, 100), (FUSION, 100, 50),
        (_kernel("flash_fwd.2"), 150, 100),
        (_kernel("flash_bwd_dkv.1"), 250, 300)])
    assert A.pallas_ns(plane) == {"flash_fwd": (200, 2),
                                  "flash_bwd_dkv": (300, 1)}


# -- a whole capture made by hand ---------------------------------------------
def _capture():
    """One chip.  Device clock: a program 10..12 ms, idle 12..15, a
    program 15..18, idle 18..18.01, a program 18.01..19.  Host plane =
    device + 1.3 ms; Tracer clock = host plane + 1000 ms."""
    ops = [(FUSION, 10 * MS, 2 * MS), (_kernel("paged_decode.4"), 15 * MS,
                                       3 * MS), (FUSION, 18.01 * MS,
                                                 0.99 * MS)]
    mods = [("jit_ragged_step(1)", 10 * MS, 2 * MS),
            ("jit_ragged_step(1)", 15 * MS, 3 * MS),
            ("jit_ragged_step(1)", 18.01 * MS, 0.99 * MS)]
    runs = [(1, 10 * MS, 12 * MS), (2, 15 * MS, 18 * MS),
            (3, 18.01 * MS, 19 * MS)]
    return A.Capture(
        planes=[trace.DevicePlane("/device:TPU:0", ops=ops, modules=mods)],
        runs={0: runs},
        anchors=[(9 * MS, int(1009 * MS)), (21 * MS, int(1021 * MS))],
        enqueues=[(s + D - 0.02 * MS, rid, 0) for rid, s, _ in runs],
        dones=[(e + D + 0.02 * MS, 0) for _, _, e in runs])


def test_a_capture_attributed_end_to_end():
    host = 1000 * MS + D            # device ns -> Tracer ns
    events = [    # us on the Tracer clock; the 12..15 ms gap lies under
        # deliver (12..13), admit_pass (13..14.5) and nothing (14.5..15)
        _ev("serve.deliver", (12 * MS + host) / 1e3, 1000),
        _ev("serve.admit_pass", (13 * MS + host) / 1e3, 1500),
        _ev("v2.dispatch", (9 * MS + host) / 1e3, 100)]
    att = A.attribute_capture(_capture(), events)
    assert att.clock_error_s == pytest.approx(0.02e-3)
    assert att.drift_s == 0 and att.stretch_mono_us == (1009e3, 1021e3)
    assert att.idle_s == pytest.approx(3.01e-3)
    assert att.long_idle_s == pytest.approx(3e-3)      # 0.01 ms: too short
    assert att.named_s == pytest.approx(2.5e-3)
    gaps = dict(att.idle_gaps)
    assert gaps["serve.deliver"] == pytest.approx(1e-3)
    assert gaps["serve.admit_pass"] == pytest.approx(1.5e-3)
    assert gaps["unattributed: after jit_ragged_step before "
                "jit_ragged_step"] == pytest.approx(0.5e-3)
    assert gaps[A.SHORT] == pytest.approx(0.01e-3)
    assert att.pallas == {"paged_decode": [pytest.approx(3e-3), 1.0]}
    (name, t0, t1), = att.pallas_events
    assert name == "paged_decode" and t1 - t0 == pytest.approx(3000)
    assert t0 == pytest.approx((15 * MS + host) / 1e3)
    run = SimpleNamespace(attribution=att)
    assert R.idle_attributed_share(run, None) == pytest.approx(100 * 2.5 / 3)


def test_without_anchors_every_gap_keeps_its_old_name():
    cap = _capture()
    cap.anchors = []
    att = A.attribute_capture(cap, [_ev("serve.deliver", 0, 10 ** 9)])
    assert att.clock_error_s is None and att.named_s == 0
    assert all(k.startswith("unattributed") for k, _ in att.idle_gaps)
    assert R.idle_attributed_share(SimpleNamespace(attribution=att),
                                   None) is None
    assert R.paged_roofline(SimpleNamespace(attribution=att, spans=[]),
                            None) is None
    with pytest.raises(ValueError):
        A.attribute_capture(A.Capture(planes=[]), [])


# -- the readers --------------------------------------------------------------
def test_serve_host_time_of_an_iteration_by_hand():
    events = [
        _ev("serve.admit_pass", 0, 300), _ev("serve.step", 300, 60_000),
        _ev("v2.ragged_step", 400, 59_800), _ev("v2.schedule", 500, 700),
        _ev("v2.h2d", 1200, 900), _ev("v2.dispatch", 2100, 1000),
        _ev("v2.fetch", 3200, 56_900), _ev("serve.deliver", 60_300, 500),
        # an iteration that only waited runs no step and counts for nothing
        _ev("serve.admit_pass", 60_800, 100),
        _ev("serve.idle_wait", 60_900, 5_000),
        _ev("serve.admit_pass", 65_900, 200),
        _ev("serve.step", 66_100, 10_000),
        _ev("v2.fetch", 67_000, 8_000), _ev("serve.deliver", 76_100, 300)]
    # 300 + (60,000 - 56,900) + 500 us; 200 + (10,000 - 8,000) + 300 us
    assert R.serve_host_ms(events) == [pytest.approx(3.9),
                                       pytest.approx(2.5)]
    run = SimpleNamespace(spans=events,
                          counters={"window_mono_us": (0, 100_000)})
    # the lower of two: lib.stats.percentile does not interpolate
    assert R.serve_host_ms_p50(run, None) == pytest.approx(2.5)
    # a traced stretch's iterations are left out of the median
    run.counters["capture_mono_us"] = (60_850, 100_000)
    assert R.serve_host_ms_p50(run, None) == pytest.approx(3.9)
    run.spans = []
    assert R.serve_host_ms_p50(run, None) is None


def test_train_step_span_leaves_the_traced_steps_out():
    events = [_ev("train.step", 0, 800_000), _ev("train.step", 900_000,
                                                 812_000),
              _ev("train.step", 1_800_000, 950_000),     # traced, slower
              _ev("train.step", 2_800_000, 814_000),
              _ev("train.dispatch", 10, 5_000)]
    run = SimpleNamespace(spans=events, counters={
        "window_mono_us": (500_000, 4_000_000),
        "capture_mono_us": (1_900_000, 2_700_000)})
    assert R.train_step_span_ms_p50(run, None) == pytest.approx(812.0)


MODEL = SimpleNamespace(num_heads=4, kv_heads=2, dim_per_head=8,
                        num_layers=3)


@pytest.mark.parametrize("window,pairs,rows", [(None, 31, 18), (7, 27, 14)])
def test_paged_least_time_of_a_hand_worked_step(window, pairs, rows):
    """Two sequences: 5 cached + 3 prefilling, 9 cached + 1 decoding."""
    from deepspeed_tpu.inference.v2.engine_v2 import step_counts

    c = step_counts([(5, 3), (9, 1)], window)
    assert (c["qk_pairs"], c["kv_rows"], c["tokens"]) == (pairs, rows, 4)
    peaks = {"flops_per_s_bf16": 1e6, "hbm_bytes_per_s": 1e4}
    least, bound = R.paged_least_time([(pairs, rows, 4)], MODEL, peaks)
    # QK^T and PV: 4 * 8 FLOPs a pair and query head, 4 heads, 3 layers
    flops = 3 * pairs * 4 * 8 * 4
    # keys and values of every row once (2 kv heads of 8, bf16), and each
    # token's query and output rows (4 heads of 8, bf16)
    nbytes = 3 * (rows * 2 * 2 * 8 * 2 + 4 * 2 * 4 * 8 * 2)
    assert least == pytest.approx(max(flops / 1e6, nbytes / 1e4))
    assert bound == ("memory" if nbytes / 1e4 > flops / 1e6 else "compute")
    # a chip with little compute is bound by it
    assert R.paged_least_time([(pairs, rows, 4)], MODEL,
                              dict(peaks, flops_per_s_bf16=10.0))[1] \
        == "compute"


def test_paged_roofline_matches_steps_to_kernel_events():
    att = SimpleNamespace(
        clock_error_s=1e-5, stretch_mono_us=(1000.0, 9000.0),
        pallas_events=[("paged_decode", 500.0, 900.0),     # before it
                       ("paged_decode", 2100.0, 2900.0),   # in step 2
                       ("flash_fwd", 2900.0, 2950.0),      # not paged
                       ("paged_decode", 8600.0, 9400.0)])  # step 3: cut off
    events = [_ev("v2.ragged_step", 400, 1000),     # began before the stretch
              _ev("v2.ragged_step", 2000, 1000),
              _ev("v2.schedule", 2010, 20, qk_pairs=31, kv_rows=18,
                  tokens=4, seqs=2),
              _ev("v2.ragged_step", 8500, 1000),    # ends after it
              _ev("v2.schedule", 8510, 20, qk_pairs=99, kv_rows=99,
                  tokens=9, seqs=1)]
    kind = next(iter(PEAKS))
    run = SimpleNamespace(attribution=att, spans=events, counters={
        "model": MODEL, "device_kind": kind})
    least, _ = R.paged_least_time([(31, 18, 4)], MODEL, PEAKS[kind])
    assert R.paged_roofline(run, None) == pytest.approx(
        100 * least / 800e-6)


# -- the second recorded trace ------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    cap = A.load_capture(str(DATA / "span_trace.xplane.pb"))
    events = json.loads((DATA / "span_trace.spans.json").read_text())[
        "traceEvents"]
    return cap, events, A.attribute_capture(cap, events)


def test_recorded_capture_carries_anchors_and_the_runtimes_events(recorded):
    cap, events, att = recorded
    assert [p.name for p in cap.planes] == ["/device:TPU:0"]
    assert len(cap.anchors) == 2 and len(cap.runs[0]) == 8
    assert len(cap.enqueues) == 8 and len(cap.dones) == 8
    assert {rid for _, rid, _ in cap.enqueues} == {r for r, _, _ in
                                                   cap.runs[0]}
    lo, hi = A.device_offset(cap.runs[0],
                             [(t, r) for t, r, _ in cap.enqueues],
                             [t for t, _ in cap.dones])
    # the device plane is early against the host plane, by about 1 ms
    assert 0.5 * MS < lo < hi < 2.5 * MS
    assert att.clock_error_s == pytest.approx((hi - lo) / 2 * 1e-9)
    assert att.clock_error_s < 0.3e-3
    assert abs(att.drift_s) < 50e-6


def test_recorded_sleep_gap_goes_to_the_sleeps_span(recorded):
    cap, events, att = recorded
    err_us = att.clock_error_s * 1e6
    sleep, = [e for e in events if e["name"] == "serve.idle_wait"]
    amap = A.anchor_map(cap.anchors)
    lo, hi = A.device_offset(cap.runs[0],
                             [(t, r) for t, r, _ in cap.enqueues],
                             [t for t, _ in cap.dones])
    gaps = [(amap.to_monotonic(a + (lo + hi) / 2) / 1e3,
             amap.to_monotonic(b + (lo + hi) / 2) / 1e3)
            for a, b in A.idle_gaps_of(cap.planes[0])]
    g0, g1 = max(gaps, key=lambda g: g[1] - g[0])
    assert g1 - g0 > 30_000
    # the device fell idle before the host's wait for it returned and the
    # sleep began, and took up work after the sleep ended and the next
    # program was dispatched: the sleep lies inside the gap, each end
    # within the stated clock error
    assert g0 <= sleep["ts"] + err_us
    assert g1 >= sleep["ts"] + sleep["dur"] - err_us
    named = dict(att.idle_gaps)
    assert named["serve.idle_wait"] == pytest.approx(sleep["dur"] / 1e6,
                                                     abs=2 * err_us / 1e6)
    # what is left of the gap is the wait before and the dispatch after
    assert named["train.sync"] > 0 and named["train.dispatch"] > 0
    assert att.named_s / att.long_idle_s > 0.9


def test_recorded_kernels_carry_their_own_names(recorded):
    _, _, att = recorded
    assert set(att.pallas) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert [v[1] for v in att.pallas.values()] == [2.0, 2.0, 2.0]
    r = trace.reduce_planes(recorded[0].planes)
    assert sum(v[0] for v in att.pallas.values()) == pytest.approx(
        r.mosaic_s)
    # the program differentiates the kernel outside any jax.checkpoint, so
    # the profiler shows the names inside jvp and transpose wrappers;
    # under a checkpoint (the train cells) they come bare
    assert {n for n, _ in r.top_ops if n.endswith(" pallas")} == {
        "jvp_flash_fwd_.1 pallas", "transpose_jvp_flash_bwd_dq__.1 pallas",
        "transpose_jvp_flash_bwd_dkv__.1 pallas"}


# -- through the tiny cells ---------------------------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return bench_tiny.make_copy(tmp_path_factory.mktemp("bench_copy"))


@pytest.fixture
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(device, "setup_compile_cache", lambda: "(off)")
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", was)


@pytest.mark.parametrize("cell,want,absent", [
    ("mistral-tiny.tiny_open",
     {"serve_host_ms_p50", "serve_step_ms_p50"}, {"train_step_span_ms_p50"}),
    ("gpt2-tiny.tiny_steps",
     {"train_step_span_ms_p50", "step_ms_p50.train"}, {"serve_host_ms_p50"})])
def test_attributed_run_rehearsed_on_the_cpu(copy, cell, want, absent,
                                             no_persistent_cache, capsys):
    """``tools/attributed_run.py`` end to end: spans on through the cell's
    configuration, a capture between two anchors, the span readers.  The
    CPU's capture has no device plane, so nothing is attributed and no
    device number comes out."""
    tool = manifest.load_code(copy, "tools", "attributed_run")
    assert tool.main(["--workload", cell, "--seed", str(2 ** 31 + 3),
                      "--seconds", "1.5", "--spans", "1", "--capture", "1"],
                     root=copy, need_chip=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0 and out["span_events"] > 0
    assert want <= set(out["metrics"]) and not absent & set(out["metrics"])
    assert all(v > 0 for v in out["metrics"].values())
    assert "breakdown" not in out and "clock_error_ms" not in out
    for name in ("idle_attributed_share", "paged_roofline",
                 "device_idle_share"):
        assert name not in out["metrics"]
    if "train_step_span_ms_p50" in want:
        # the span is train_batch alone; the outside clock adds the wait
        assert out["metrics"]["train_step_span_ms_p50"] <= \
            out["metrics"]["step_ms_p50.train"] * 1.05


def test_spans_off_leaves_nothing_to_read(copy, no_persistent_cache, capsys):
    tool = manifest.load_code(copy, "tools", "attributed_run")
    assert tool.main(["--workload", "mistral-tiny.tiny_closed", "--seed",
                      "11", "--seconds", "1", "--spans", "0", "--capture",
                      "0"], root=copy, need_chip=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["span_events"] == 0
    assert out["metrics"] == {}
    assert {"serve_tokens_per_s", "ttft_p95_ms", "token_gap_p95_ms",
            "setup_s"} <= set(out["end_to_end"])


def test_serve_driver_spans_reach_the_reader(copy, no_persistent_cache):
    """What ``benchmark/run.py --trace 1`` hands a reader today: the serve
    driver's ``Run.spans`` already carry the new spans."""
    from benchmark.lib import harness

    cell = manifest.load_cell(copy, "mistral-tiny.tiny_closed")
    run = harness.DRIVERS["closed_loop"](cell, 5, 1.5, True,
                                         time.perf_counter(),
                                         device.CompileCounter())
    assert R.serve_host_ms_p50(run, cell) > 0
    assert R.idle_attributed_share(run, cell) is None
    assert R.train_step_span_ms_p50(run, cell) is None
