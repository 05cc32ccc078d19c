"""Distributed request/step tracing + flight recorder
(telemetry/tracing.py, telemetry/flight.py; docs/OBSERVABILITY.md
"Tracing & flight recorder").

Acceptance criteria covered here:
* serve ≥ 4 concurrent requests with tracing on → the exported Chrome
  trace parses, and each request's queue_wait/prefill/decode/request
  spans share its trace_id;
* a train run's ``train.step`` spans carry the matching StepRecord step
  ids;
* a forced serve-loop hang fires the watchdog within its deadline and
  the bundle carries all-thread stacks + a non-empty span ring;
* with tracing disabled the hot path returns the shared NULL_SPAN and
  retains no allocations.
"""

import gc
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.telemetry.flight import (FlightRecorder, Watchdog,
                                            dump_bundle)
from deepspeed_tpu.telemetry.tracing import (EVENT_NAMES, NULL_SPAN,
                                             SPAN_NAMES, Tracer)


# ----------------------------------------------------------------------
# tracer unit behavior
# ----------------------------------------------------------------------
def test_span_export_is_wellformed_chrome_trace(tmp_path):
    tr = Tracer(enabled=True)
    tid = tr.new_trace_id()
    root = tr.span("serve.request", tid).set(uid=1)
    with tr.span("serve.queue_wait", tid, root):
        pass
    tr.instant("serve.enqueue", tid, uid=1)
    root.end(outcome="completed")

    path = tr.export_chrome_trace(str(tmp_path / "t.trace.json"))
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in spans} == {"serve.request",
                                          "serve.queue_wait"}
    assert all(e["args"]["trace_id"] == tid for e in spans + instants)
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    # parent chain: queue_wait points at the request root span
    child = next(e for e in spans if e["name"] == "serve.queue_wait")
    root_ev = next(e for e in spans if e["name"] == "serve.request")
    assert child["args"]["parent_id"] == root_ev["args"]["span_id"]
    assert root_ev["args"]["outcome"] == "completed"
    # thread metadata rows name the emitting thread
    assert any(m["name"] == "process_name" for m in metas)
    assert any(m["name"] == "thread_name" for m in metas)
    # structural validation is the same check telemetry_check ships
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_check", os.path.join(os.path.dirname(__file__), "..",
                                        "tools", "telemetry_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.validate_chrome_trace(path) == []


def test_export_survives_non_json_span_args(tmp_path):
    """One exotic span arg (numpy scalar, object, ...) must not abort
    the whole export at shutdown — args degrade to repr(), same contract
    as flight.dump_bundle's ring.json."""
    tr = Tracer(enabled=True)
    tr.span("serve.step").set(shape=np.int64(4), obj=object()).end()

    path = tr.export_chrome_trace(str(tmp_path / "weird.trace.json"))
    with open(path) as f:
        trace = json.load(f)
    ev = next(e for e in trace["traceEvents"] if e["ph"] == "X")
    assert "4" in str(ev["args"]["shape"])  # repr'd numpy scalar
    assert "object" in ev["args"]["obj"]


def test_span_end_idempotent_and_bounded_buffer():
    tr = Tracer(enabled=True, max_events=8)
    sp = tr.span("serve.step")
    sp.end()
    sp.end()      # double-end (crash paths) must not duplicate
    assert len(tr.snapshot()) == 1
    for _ in range(20):
        tr.span("serve.step").end()
    assert len(tr.snapshot()) == 8      # bounded
    assert tr.dropped_events == 13      # 21 emitted, 8 kept


def test_disabled_tracer_fast_path_no_allocation():
    tr = Tracer(enabled=False)
    # identity: the disabled path returns the shared singleton
    assert tr.span("serve.step") is NULL_SPAN
    assert tr.span("train.step", "tid") is NULL_SPAN
    assert NULL_SPAN.set(a=1) is NULL_SPAN
    with tr.span("serve.step") as sp:
        assert sp is NULL_SPAN
    tr.instant("serve.enqueue", "tid", uid=1)
    assert tr.snapshot() == []

    # the serve-loop hot-path shape (span + end per step) retains nothing
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in range(5000):
        s = tr.span("serve.step", "tid")
        s.end()
    gc.collect()
    after = sys.getallocatedblocks()
    assert after - before < 50, f"disabled tracer leaked {after - before}"
    assert tr.snapshot() == []


def test_summary_rollup():
    tr = Tracer(enabled=True)
    for _ in range(3):
        tr.span("serve.prefill").end()
    tr.span("serve.decode").end()
    s = tr.summary()
    assert s["serve.prefill"]["count"] == 3
    assert s["serve.decode"]["count"] == 1
    assert s["serve.prefill"]["total_ms"] >= 0.0


def test_span_track_named_for_creating_thread():
    """A span created on one thread but ended on another (submit() opens
    request spans the serve loop closes) renders on a track named for
    the *creating* thread."""
    tr = Tracer(enabled=True)
    sp = tr.span("serve.request")
    t = threading.Thread(target=sp.end, name="ds-serve-loop")
    t.start()
    t.join()
    trace = tr.chrome_trace()
    names = {e["tid"]: e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    ev = next(e for e in trace["traceEvents"] if e["ph"] == "X")
    assert names[ev["tid"]] == threading.current_thread().name


# ----------------------------------------------------------------------
# flight recorder + watchdog
# ----------------------------------------------------------------------
def test_flight_ring_bounded_keeps_newest():
    ring = FlightRecorder(capacity=4)
    tr = Tracer(enabled=True, ring=ring)
    for i in range(10):
        tr.span("serve.step").set(i=i).end()
    events = ring.snapshot()
    assert len(events) == 4
    assert [e["args"]["i"] for e in events] == [6, 7, 8, 9]


def test_make_span_recorder_tracing_only_skips_ring():
    """The shared bootstrap factory: flight alone enables span recording;
    a tracing-only config gets NO ring — nothing reads it (dump paths
    are gated on flight.enabled), so the hot path skips the per-emit
    lock + append and the 2048-event retention."""
    from deepspeed_tpu.telemetry import make_span_recorder

    tr, ring = make_span_recorder(tracing_enabled=True,
                                  flight_enabled=False)
    assert tr.enabled and ring is None
    tr.span("serve.step").end()             # ring-less emit still records
    assert len(tr.snapshot()) == 1

    tr2, ring2 = make_span_recorder(tracing_enabled=False,
                                    flight_enabled=True, ring_size=4)
    assert tr2.enabled and ring2 is not None and ring2.capacity == 4
    tr2.span("serve.step").end()
    assert len(ring2) == 1

    tr3, ring3 = make_span_recorder(tracing_enabled=False,
                                    flight_enabled=False)
    assert not tr3.enabled and ring3 is None


def test_dump_bundle_contents(tmp_path):
    ring = FlightRecorder()
    tr = Tracer(enabled=True, ring=ring)
    tr.span("serve.step").end()
    bundle = dump_bundle(str(tmp_path), "manual", ring=ring,
                         error=RuntimeError("boom"))
    files = set(os.listdir(bundle))
    assert {"manifest.json", "stacks.txt", "ring.json"} <= files
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["reason"] == "manual"
    assert "boom" in manifest["error"]
    assert manifest["ring_events"] == 1
    stacks = open(os.path.join(bundle, "stacks.txt")).read()
    assert "MainThread" in stacks
    assert "test_dump_bundle_contents" in stacks  # this frame captured
    ring_doc = json.load(open(os.path.join(bundle, "ring.json")))
    assert ring_doc["events"][0]["name"] == "serve.step"


def test_watchdog_fires_within_deadline_and_rearms(tmp_path):
    ring = FlightRecorder()
    tr = Tracer(enabled=True, ring=ring)
    tr.span("train.step").end()           # something for the ring
    fired = []
    wd = Watchdog("t", deadline_s=0.2, output_dir=str(tmp_path),
                  ring=ring, tracer=tr, poll_s=0.02,
                  on_fire=fired.append).start()
    try:
        # healthy phase: beat faster than the deadline → no fire
        for _ in range(10):
            wd.beat()
            time.sleep(0.03)
        assert wd.fire_count == 0
        # stall: stop beating → exactly one bundle, within ~deadline
        t0 = time.monotonic()
        deadline = t0 + 5.0
        while wd.fire_count == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert wd.fire_count == 1
        assert time.monotonic() - t0 < 2.0      # 0.2s deadline + slack
        time.sleep(0.3)
        assert wd.fire_count == 1               # one bundle per stall
        # recovery re-arms: a new stall fires again
        wd.beat()
        time.sleep(0.5)
        assert wd.fire_count == 2
    finally:
        wd.stop()
    assert len(fired) == wd.fire_count
    bundle = fired[0]
    stacks = open(os.path.join(bundle, "stacks.txt")).read()
    assert "MainThread" in stacks               # all-thread stacks
    ring_doc = json.load(open(os.path.join(bundle, "ring.json")))
    assert len(ring_doc["events"]) > 0          # non-empty span ring
    # the stall is also visible in the trace itself
    assert any(e["name"] == "watchdog.fire" for e in tr.snapshot())


def test_watchdog_restart_after_stop_still_fires(tmp_path):
    """A stop()ed watchdog can be re-armed: start() clears the stop
    event, else the fresh thread exits on its first wait() and
    monitoring dies silently while beat()/resume() appear to work."""
    wd = Watchdog("t", deadline_s=0.2, output_dir=str(tmp_path),
                  poll_s=0.02)
    wd.resume()
    wd.stop()
    wd.resume()                     # re-arm after stop()
    try:
        t0 = time.monotonic()
        while wd.fire_count == 0 and time.monotonic() - t0 < 5.0:
            time.sleep(0.01)
        assert wd.fire_count == 1   # restarted thread really monitors
    finally:
        wd.stop()


def test_admission_block_span_not_admitted_on_close():
    """A blocking offer() woken by close() is a rejection — its
    serve.admission_block span must not claim admitted=True."""
    from deepspeed_tpu.serving.admission import (AdmissionConfig,
                                                 AdmissionController)
    from deepspeed_tpu.serving.request import (GenerationRequest, QueueFull,
                                               ResponseStream,
                                               SamplingParams)

    ctl = AdmissionController(AdmissionConfig(max_queue_size=1,
                                              queue_policy="block"))
    tr = Tracer(enabled=True)
    ctl.tracer = tr

    def req(uid):
        return GenerationRequest(uid=uid, prompt=[1, 2],
                                 params=SamplingParams(max_new_tokens=2),
                                 stream=ResponseStream(uid),
                                 trace_id=tr.new_trace_id())

    ctl.offer(req(0))                      # fills the queue
    errs = []

    def blocked_offer():
        try:
            ctl.offer(req(1), timeout=10.0)
        except QueueFull as e:
            errs.append(e)

    t = threading.Thread(target=blocked_offer)
    t.start()
    time.sleep(0.15)                       # let it block on the full queue
    ctl.close()                            # wakes the waiter → rejection
    t.join(timeout=10)
    assert len(errs) == 1
    span = next(e for e in tr.snapshot()
                if e["name"] == "serve.admission_block")
    assert span["args"]["admitted"] is False


def test_watchdog_pause_suppresses_fire(tmp_path):
    """pause() silences stall detection (inter-step gaps are not hangs);
    resume() re-arms with a fresh deadline clock."""
    wd = Watchdog("t", deadline_s=0.1, output_dir=str(tmp_path),
                  poll_s=0.02)
    wd.resume()                   # starts the thread, armed
    try:
        wd.pause()
        time.sleep(0.4)           # way past the deadline while paused
        assert wd.fire_count == 0
        wd.resume()               # fresh clock: no instant fire either
        time.sleep(0.05)
        assert wd.fire_count == 0
        deadline = time.monotonic() + 5.0
        while wd.fire_count == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert wd.fire_count == 1  # unpaused stall still detected
    finally:
        wd.stop()


def test_flight_only_config_still_populates_ring(tmp_path):
    """flight.enabled without tracing.enabled must still record spans
    into the ring (an empty ring.json defeats the flight recorder), but
    must not export a trace file."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.telemetry import Telemetry

    unwanted = str(tmp_path / "explicitly_disabled.trace.json")
    tel = Telemetry(TelemetryConfig(
        enabled=True,
        # trace_path under a DISABLED tracing block: the user said no
        # trace file — flight-only span recording must not write one
        tracing={"enabled": False, "trace_path": unwanted},
        flight={"enabled": True, "deadline_s": 3600.0,
                "output_dir": str(tmp_path)}))
    assert tel.tracer.enabled
    tel.tracer.span("serve.step").end()
    assert len(tel.flight_ring) == 1
    assert tel.export_trace() is None   # tracing block disabled
    bundle = tel.dump_flight("manual")
    ring_doc = json.load(open(os.path.join(bundle, "ring.json")))
    assert len(ring_doc["events"]) == 1
    tel.close()
    assert not os.path.exists(unwanted)


# ----------------------------------------------------------------------
# serving end-to-end (acceptance)
# ----------------------------------------------------------------------
def _tiny_engine(num_blocks=64, block_size=4, max_seqs=8, budget=16,
                 max_context=64):
    from deepspeed_tpu.inference.v2 import build_engine
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("llama-tiny", num_layers=1)
    eng = build_engine(
        model, {"dtype": "float32",
                "state_manager": {"max_tracked_sequences": max_seqs,
                                  "max_ragged_batch_size": budget},
                "memory_config": {"num_blocks": num_blocks,
                                  "block_size": block_size},
                "max_context": max_context}, seed=0)
    return model, eng


def test_serving_trace_e2e_four_concurrent_requests(tmp_path):
    """4 concurrent requests with tracing on: the exported trace parses,
    and each request's queue→prefill→decode→finish chain shares its
    trace_id."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.serving import InferenceServer, SamplingParams
    from deepspeed_tpu.telemetry import Telemetry

    trace_path = str(tmp_path / "serve.trace.json")
    tel = Telemetry(TelemetryConfig(
        enabled=True, tracing={"enabled": True, "trace_path": trace_path}))
    model, eng = _tiny_engine()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, model.vocab_size, size=n).tolist()
               for n in (5, 9, 3, 7)]
    srv = InferenceServer(eng, telemetry=tel).start()
    try:
        outs = {}

        def run(i):
            stream = srv.submit(prompts[i],
                                SamplingParams(max_new_tokens=6))
            outs[i] = (stream.trace_id, [t for t in stream])

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        srv.stop()
    tel.close()  # exports the trace

    with open(trace_path) as f:
        trace = json.load(f)
    events = [e for e in trace["traceEvents"] if e["ph"] in ("X", "i")]
    # every emitted name comes from the frozen vocabulary
    assert {e["name"] for e in events} <= set(SPAN_NAMES) | set(EVENT_NAMES)
    by_trace = {}
    for e in events:
        by_trace.setdefault(e["args"].get("trace_id"), []).append(e)
    for i in range(4):
        trace_id, toks = outs[i]
        assert trace_id and len(toks) == 6
        names = [e["name"] for e in by_trace[trace_id]]
        for want in ("serve.request", "serve.queue_wait", "serve.prefill",
                     "serve.decode", "serve.enqueue", "serve.first_token",
                     "serve.finish"):
            assert want in names, (want, sorted(set(names)))
        root = next(e for e in by_trace[trace_id]
                    if e["name"] == "serve.request")
        assert root["args"]["outcome"] == "completed"
        assert root["args"]["generated"] == 6
        # phases nest inside the request span's window
        for e in by_trace[trace_id]:
            if e["ph"] == "X" and e["name"] != "serve.request":
                assert e["ts"] >= root["ts"] - 1.0
                assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1.0
        # one serve.emit instant per streamed token
        assert sum(1 for e in by_trace[trace_id]
                   if e["name"] == "serve.emit") == 6
    # loop-level step spans exist and engine dispatches joined the trace
    step_names = {e["name"] for e in events}
    assert "serve.step" in step_names
    assert "v2.ragged_step" in step_names


def test_serve_loop_hang_fires_watchdog_with_forensics(tmp_path):
    """Forced hang: the watchdog fires within its deadline; the bundle
    has all-thread stacks (including the wedged serve loop) and a
    non-empty span ring."""
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    model, eng = _tiny_engine()
    release = threading.Event()
    orig_launch = eng.launch    # the first half of the greedy loop's step

    def hang(*a, **kw):
        release.wait(30)
        return orig_launch(*a, **kw)

    flight_dir = str(tmp_path / "flight")
    srv = InferenceServer(eng, {
        "tracing": {"enabled": True},
        "flight": {"enabled": True, "deadline_s": 0.3, "poll_s": 0.05,
                   "output_dir": flight_dir}}).start()
    try:
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, model.vocab_size, size=5).tolist()
        # warm request: the process's first engine.step pays the jit
        # compile and is deliberately unmonitored — it must complete
        # without the watchdog reporting the compile as a hang
        srv.submit(prompt, SamplingParams(max_new_tokens=1)).result(
            timeout=120)
        assert srv._watchdog.fire_count == 0
        eng.launch = hang
        t0 = time.monotonic()
        stream = srv.submit(prompt, SamplingParams(max_new_tokens=2))
        while srv._watchdog.fire_count == 0 \
                and time.monotonic() - t0 < 10.0:
            time.sleep(0.02)
        assert srv._watchdog.fire_count >= 1
        assert time.monotonic() - t0 < 5.0      # deadline 0.3s + slack
        bundle = srv._watchdog.bundles[0]
        manifest = json.load(open(os.path.join(bundle, "manifest.json")))
        assert manifest["reason"] == "watchdog"
        assert manifest["stalled_s"] >= 0.3
        stacks = open(os.path.join(bundle, "stacks.txt")).read()
        assert "ds-serve-loop" in stacks        # the wedged thread
        assert "hang" in stacks                 # ...inside the fake step
        ring_doc = json.load(open(os.path.join(bundle, "ring.json")))
        assert len(ring_doc["events"]) > 0      # enqueue/admit spans
        assert srv.metrics.flight_dumps >= 1
    finally:
        release.set()
        stream.result(timeout=60)
        srv.stop()


def test_first_step_kv_exhaustion_keeps_compile_skip(tmp_path):
    """A first engine.step that exits with KVCacheExhausted ran nothing
    (scheduler rolled back), so it must NOT consume the per-process
    first-compile watchdog skip — the retry is the step that actually
    pays the jit compile and still needs the watchdog disarmed."""
    from deepspeed_tpu.inference.v2.ragged import KVCacheExhausted
    from deepspeed_tpu.serving import (InferenceServer, SamplingParams,
                                       ServingError)

    model, eng = _tiny_engine()
    orig_launch = eng.launch    # the first half of the greedy loop's step
    paused_at_call = []

    def exhaust_first(*a, **kw):
        paused_at_call.append(srv._watchdog._paused)
        if len(paused_at_call) == 1:
            raise KVCacheExhausted("synthetic: no pages")
        return orig_launch(*a, **kw)

    eng.launch = exhaust_first
    srv = InferenceServer(eng, {
        "flight": {"enabled": True, "deadline_s": 300.0,
                   "output_dir": str(tmp_path / "flight")}}).start()
    try:
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, model.vocab_size, size=4).tolist()
        # only runner + exhaustion => _preempt_one fails it fast
        with pytest.raises(ServingError):
            srv.submit(prompt, SamplingParams(max_new_tokens=2)).result(
                timeout=60)
        # the real first compile happens on this request's steps
        srv.submit(prompt, SamplingParams(max_new_tokens=2)).result(
            timeout=120)
    finally:
        eng.launch = orig_launch
        srv.stop()
    assert len(paused_at_call) >= 3
    assert paused_at_call[0]      # warm skip armed for the exhausted try
    assert paused_at_call[1]      # ...and STILL armed for the real compile
    assert not paused_at_call[2]  # consumed once a step actually ran
    assert srv._watchdog.fire_count == 0


def test_serve_loop_crash_writes_flight_bundle(tmp_path, monkeypatch):
    """The crash handler leaves the same forensics bundle behind."""
    from deepspeed_tpu.serving import (InferenceServer, SamplingParams,
                                       ServingError)

    model, eng = _tiny_engine()
    flight_dir = str(tmp_path / "flight")
    srv = InferenceServer(eng, {
        "tracing": {"enabled": True},
        "flight": {"enabled": True, "deadline_s": 30.0,
                   "output_dir": flight_dir}}).start()
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, model.vocab_size, size=4).tolist()

    def boom(*a, **kw):
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(eng, "launch", boom)
    s = srv.submit(prompt, SamplingParams(max_new_tokens=4))
    with pytest.raises(ServingError):
        s.result(timeout=60)
    bundles = [d for d in os.listdir(flight_dir)
               if d.startswith("flight_serve_crash")]
    assert len(bundles) == 1
    manifest = json.load(
        open(os.path.join(flight_dir, bundles[0], "manifest.json")))
    assert manifest["reason"] == "serve_crash"
    assert "injected engine failure" in manifest["error"]
    with pytest.raises(RuntimeError, match="serve loop died"):
        srv.stop()
    assert srv.metrics.flight_dumps == 1
    # the crash handler paused the watchdog: the dead loop's missing
    # heartbeats must not echo the crash as a second 'watchdog' bundle
    assert srv._watchdog._paused
    assert srv._watchdog.fire_count == 0


def test_hub_flight_config_wins_over_server_blocks(tmp_path):
    """With a telemetry hub passed, the server's own tracing/flight
    blocks are ignored — a server-level flight block paired with the
    hub's disabled tracer would dump forever-empty rings."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.serving import InferenceServer
    from deepspeed_tpu.telemetry import Telemetry

    tel = Telemetry(TelemetryConfig(enabled=True))   # no tracing, no flight
    model, eng = _tiny_engine()
    srv = InferenceServer(
        eng, {"flight": {"enabled": True, "deadline_s": 0.1,
                         "output_dir": str(tmp_path)}}, telemetry=tel)
    assert srv.tracer is tel.tracer
    assert srv._watchdog is None            # hub has no flight block
    tel.close()


def test_hubless_watchdog_defaults_match_hub_factory(tmp_path):
    """The hub-less server wires its watchdog through the same
    make_watchdog factory as the hub: falsy config values (deadline_s 0,
    empty output_dir) fall back instead of producing a 0-second deadline
    that fires on a healthy idle loop and dumps bundles into cwd."""
    from deepspeed_tpu.serving import InferenceServer

    _, eng = _tiny_engine()
    srv = InferenceServer(eng, {
        "flight": {"enabled": True, "deadline_s": 0, "output_dir": ""}})
    assert srv._watchdog is not None
    assert srv._watchdog.deadline_s == 60.0
    assert srv._flight_dir == "./dstpu_flight"
    assert srv._watchdog.output_dir == "./dstpu_flight"


# ----------------------------------------------------------------------
# training side (acceptance: spans ↔ StepRecords)
# ----------------------------------------------------------------------
def test_train_step_spans_match_step_records(tmp_path):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.telemetry import read_jsonl

    jsonl = str(tmp_path / "steps.jsonl")
    trace_path = str(tmp_path / "train.trace.json")
    model = get_model_config("gpt2-tiny")
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
        "telemetry": {
            "enabled": True, "jsonl_path": jsonl, "measure_flops": False,
            "tracing": {"enabled": True, "trace_path": trace_path},
        },
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, size=(8, 33), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1],
             "labels": ids[:, 1:].astype(np.int32)}
    for _ in range(3):
        loss = engine.train_batch(batch)
    assert np.isfinite(float(np.asarray(loss)))
    engine.destroy()          # telemetry.close() exports the trace

    record_steps = [r["step"] for r in read_jsonl(jsonl)]
    assert record_steps == [1, 2, 3]
    with open(trace_path) as f:
        events = [e for e in json.load(f)["traceEvents"]
                  if e["ph"] in ("X", "i")]
    assert {e["name"] for e in events} <= set(SPAN_NAMES) | set(EVENT_NAMES)
    step_spans = [e for e in events if e["name"] == "train.step"]
    # cross-link: span step args == the StepRecord step ids, 1:1
    assert [e["args"]["step"] for e in step_spans] == record_steps
    # all train spans share the engine's run trace id
    trace_ids = {e["args"]["trace_id"] for e in events}
    assert len(trace_ids) == 1
    names = {e["name"] for e in events}
    assert {"train.data_ingest", "train.dispatch", "train.sync",
            "train.telemetry"} <= names
    # phase spans nest inside their step span
    for phase in (e for e in events
                  if e["ph"] == "X" and e["name"] != "train.step"):
        parent = phase["args"].get("parent_id")
        assert any(s["args"]["span_id"] == parent for s in step_spans)


def test_train_watchdog_skips_first_step_after_checkpoint_resume(
        tmp_path, monkeypatch):
    """The first ``train_batch`` of a *process* pays the full XLA compile
    even when ``global_steps`` was restored from a checkpoint — the
    watchdog must stay disarmed for it (the guard is per-process, not
    ``global_steps``), else a resume writes a spurious hang bundle."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("gpt2-tiny")
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
        "telemetry": {
            "enabled": True,
            "flight": {"enabled": True, "deadline_s": 3600.0,
                       "output_dir": str(tmp_path / "flight")},
        },
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    try:
        assert engine._watchdog is not None
        engine.global_steps = 1000      # what load_checkpoint restores
        resumes = []
        orig_resume = engine._watchdog.resume
        monkeypatch.setattr(
            engine._watchdog, "resume",
            lambda: (resumes.append(1), orig_resume())[1])
        rng = np.random.default_rng(0)
        ids = rng.integers(0, model.vocab_size, size=(8, 33),
                           dtype=np.int32)
        batch = {"input_ids": ids[:, :-1],
                 "labels": ids[:, 1:].astype(np.int32)}
        engine.train_batch(batch)
        assert resumes == []            # compile step: never armed
        engine.train_batch(batch)
        assert resumes == [1]           # second step: armed as usual
    finally:
        engine.destroy()


def test_engine_destroy_during_exception_dumps_bundle(tmp_path):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    flight_dir = str(tmp_path / "flight")
    model = get_model_config("gpt2-tiny")
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
        "telemetry": {
            "enabled": True,
            "tracing": {"enabled": True},
            "flight": {"enabled": True, "deadline_s": 3600.0,
                       "output_dir": flight_dir},
        },
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    try:
        try:
            raise RuntimeError("train step blew up")
        finally:
            engine.destroy()    # the usual `finally: destroy()` pattern
    except RuntimeError:
        pass
    bundles = [d for d in os.listdir(flight_dir)
               if d.startswith("flight_engine_crash")]
    assert len(bundles) == 1
    manifest = json.load(
        open(os.path.join(flight_dir, bundles[0], "manifest.json")))
    assert "train step blew up" in manifest["error"]
