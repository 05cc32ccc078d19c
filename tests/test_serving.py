"""Serving layer (MII analog): streams, admission, preemption, metrics.

The correctness oracle mirrors test_inference_v2: everything the async
serve loop produces under greedy sampling must be BIT-IDENTICAL to the
engine's one-shot ``generate()`` with the same weights — across thread
interleavings, admission waves, and KV-exhaustion preemptions.
"""

import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import build_engine
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.serving import (DeadlineExceeded, InferenceServer,
                                   QueueFull, RequestCancelled,
                                   SamplingParams, ServingError,
                                   ServingMetrics)


def _tiny_engine(num_blocks=64, block_size=4, max_seqs=8, budget=16,
                 max_context=64, seed=0):
    model = get_model_config("llama-tiny", num_layers=1)
    eng = build_engine(
        model, {"dtype": "float32",
                "state_manager": {"max_tracked_sequences": max_seqs,
                                  "max_ragged_batch_size": budget},
                "memory_config": {"num_blocks": num_blocks,
                                  "block_size": block_size},
                "max_context": max_context}, seed=seed)
    return model, eng


def _prompts(model, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, model.vocab_size, size=n).tolist()
            for n in sizes]


def test_streaming_matches_generate_one_shot():
    """Iterated stream tokens == blocking result() == engine.generate()."""
    model, eng = _tiny_engine()
    prompts = _prompts(model, (5, 11, 3))
    ref = eng.generate(prompts, max_new_tokens=6)
    srv = InferenceServer(eng).start()
    try:
        streamed = {}

        def consume(i, stream):
            streamed[i] = [tok for tok in stream]  # incremental iterator

        streams = [srv.submit(p, SamplingParams(max_new_tokens=6))
                   for p in prompts]
        threads = [threading.Thread(target=consume, args=(i, s))
                   for i, s in enumerate(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert [streamed[i] for i in range(3)] == ref
        assert [s.result(timeout=1) for s in streams] == ref
    finally:
        srv.stop()
    assert eng.free_blocks == eng.cfg.num_blocks - 1


def test_e2e_concurrent_streaming_preemption_parity():
    """The acceptance-criteria run: 8 threads submit concurrently, tokens
    stream incrementally (a first token lands before any other request
    finishes), a tiny KV pool forces ≥1 preemption that recovers, final
    outputs are bit-identical to one-shot greedy generate(), and the
    metrics snapshot shows nonzero TTFT/TPOT/preemption counters."""
    n_req, new = 8, 12
    # 23 usable blocks: eight 8-token prompts admit (2 blocks each) but
    # grow to ceil(20/4)=5 blocks → demand 40 > 23 → forced preemption
    model, eng = _tiny_engine(num_blocks=24, block_size=4, max_seqs=8,
                              budget=32, max_context=32)
    prompts = _prompts(model, [8] * n_req, seed=7)
    ref = eng.generate(prompts, max_new_tokens=new)
    assert eng.free_blocks == 23

    srv = InferenceServer(eng).start()
    outs = {}
    first_token_at = {}
    finished_at = {}

    def submit_and_consume(i):
        stream = srv.submit(prompts[i], SamplingParams(max_new_tokens=new))
        toks = []
        for tok in stream:
            if not toks:
                first_token_at[i] = time.monotonic()
            toks.append(tok)
        finished_at[i] = time.monotonic()
        outs[i] = toks

    try:
        threads = [threading.Thread(target=submit_and_consume, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        srv.stop()

    assert [outs[i] for i in range(n_req)] == ref  # bit-identical greedy
    # continuous batching: someone's first token precedes someone else's
    # completion (tokens interleave across requests, not one-at-a-time)
    assert any(first_token_at[a] < finished_at[b]
               for a in range(n_req) for b in range(n_req) if a != b)
    snap = srv.metrics.snapshot()
    assert snap["preemptions"] >= 1          # KV exhaustion recovered
    assert snap["completed"] == n_req
    assert snap["ttft"]["count"] == n_req and snap["ttft"]["p50"] > 0
    assert snap["tpot"]["count"] == n_req and snap["tpot"]["p50"] > 0
    assert snap["tokens_out"] == n_req * new
    assert eng.free_blocks == 23             # no leaked pages
    assert eng.state_manager.n_active == 0


def test_interleaved_prefill_decode_waves():
    """Submitters arrive while earlier requests are mid-decode: outputs
    still match one-shot generate() per prompt."""
    model, eng = _tiny_engine(max_seqs=4, budget=16)
    prompts = _prompts(model, (9, 4, 13, 6, 3, 11), seed=3)
    ref = eng.generate(prompts, max_new_tokens=5)
    srv = InferenceServer(eng).start()
    try:
        streams = []
        for i, p in enumerate(prompts):
            streams.append(srv.submit(p, SamplingParams(max_new_tokens=5)))
            time.sleep(0.05)  # arrivals interleave with running decode
        outs = [s.result(timeout=120) for s in streams]
    finally:
        srv.stop()
    assert outs == ref


def test_cancellation_mid_stream():
    model, eng = _tiny_engine()
    srv = InferenceServer(eng).start()
    try:
        [p] = _prompts(model, (6,))
        stream = srv.submit(p, SamplingParams(max_new_tokens=40))
        it = iter(stream)
        got = [next(it)]           # wait until it's demonstrably running
        stream.cancel()
        with pytest.raises(RequestCancelled):
            for tok in it:
                got.append(tok)
        with pytest.raises(RequestCancelled):
            stream.result(timeout=10)
        assert len(stream.tokens) >= len(got)  # delivered tokens readable
        deadline = time.monotonic() + 10
        while eng.state_manager.n_active and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.state_manager.n_active == 0  # slot + pages reclaimed
    finally:
        srv.stop()
    snap = srv.metrics.snapshot()
    assert snap["cancelled"] == 1


def test_cancel_while_queued():
    """Cancelling before admission: request leaves the queue unserved."""
    model, eng = _tiny_engine(max_seqs=1)
    srv = InferenceServer(eng)
    long, short = _prompts(model, (6, 4))
    s1 = srv.submit(long, SamplingParams(max_new_tokens=32))
    s2 = srv.submit(short, SamplingParams(max_new_tokens=4))
    s2.cancel()                    # cancelled while queued (server not up)
    srv.start()
    try:
        assert len(s1.result(timeout=120)) == 32
        with pytest.raises(RequestCancelled):
            s2.result(timeout=10)
        assert s2.tokens == []
    finally:
        srv.stop()


def test_deadline_expiry():
    model, eng = _tiny_engine()
    srv = InferenceServer(eng).start()
    try:
        [p] = _prompts(model, (5,))
        stream = srv.submit(p, SamplingParams(max_new_tokens=50),
                            deadline_s=0.3)
        with pytest.raises(DeadlineExceeded):
            stream.result(timeout=60)
        ok = srv.submit(p, SamplingParams(max_new_tokens=3))
        assert len(ok.result(timeout=60)) == 3   # server survives expiry
    finally:
        srv.stop()
    assert srv.metrics.snapshot()["expired"] == 1


def test_queue_full_reject_policy():
    model, eng = _tiny_engine()
    srv = InferenceServer(eng, {"admission": {"max_queue_size": 2}})
    [p] = _prompts(model, (4,))
    srv.submit(p), srv.submit(p)   # server not started: queue only fills
    with pytest.raises(QueueFull):
        srv.submit(p)
    assert srv.metrics.snapshot()["rejected"] == 1


def test_submit_validation():
    model, eng = _tiny_engine(num_blocks=8, block_size=4, max_context=16)
    srv = InferenceServer(eng)
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit([])
    with pytest.raises(ValueError, match="KV blocks"):
        srv.submit(list(range(1, 10)),
                   SamplingParams(max_new_tokens=4096))
    # degenerate sampling params fail at the API boundary — inside the
    # serve loop they would crash it and fail every in-flight request
    with pytest.raises(ValueError, match="top_p"):
        srv.submit([1, 2], SamplingParams(temperature=0.8, top_p=0.0))
    with pytest.raises(ValueError, match="top_k"):
        srv.submit([1, 2], SamplingParams(temperature=0.8, top_k=-1))


def test_heterogeneous_sampling_batch():
    """Greedy and nucleus requests coexist in one ragged batch; greedy
    outputs stay bit-identical to generate(), sampled outputs are valid
    and deterministic per seed."""
    model, eng = _tiny_engine()
    prompts = _prompts(model, (5, 7), seed=11)
    ref = eng.generate([prompts[0]], max_new_tokens=6)
    outs = {}
    for attempt in range(2):
        srv = InferenceServer(eng).start()
        try:
            g = srv.submit(prompts[0], SamplingParams(max_new_tokens=6))
            s = srv.submit(prompts[1], SamplingParams(
                max_new_tokens=6, temperature=0.8, top_p=0.9, top_k=50,
                seed=123))
            outs[attempt] = (g.result(timeout=120), s.result(timeout=120))
        finally:
            srv.stop()
        assert outs[attempt][0] == ref[0]
        assert all(0 <= t < model.vocab_size for t in outs[attempt][1])
    assert outs[0][1] == outs[1][1]  # same seed → same sampled tokens


def test_graceful_drain_vs_abort():
    model, eng = _tiny_engine()
    [p] = _prompts(model, (5,))
    srv = InferenceServer(eng).start()
    streams = [srv.submit(p, SamplingParams(max_new_tokens=8))
               for _ in range(3)]
    srv.stop(drain=True, timeout=120)         # drain: all complete
    assert all(len(s.result(timeout=1)) == 8 for s in streams)

    srv2 = InferenceServer(eng).start()
    streams2 = [srv2.submit(p, SamplingParams(max_new_tokens=50))
                for _ in range(3)]
    srv2.stop(drain=False, timeout=60)        # abort: all cancelled
    for s in streams2:
        with pytest.raises(RequestCancelled):
            s.result(timeout=1)
    assert eng.free_blocks == eng.cfg.num_blocks - 1
    with pytest.raises(RuntimeError, match="already stopped"):
        srv2.start()                          # no silent dead restarts


def test_priority_scheduling_order():
    """Higher-priority requests admitted from a contended queue first."""
    model, eng = _tiny_engine(max_seqs=8)
    sched = eng.scheduler
    mgr = eng.state_manager
    for uid, prio in ((1, 0), (2, 5), (3, 1)):
        mgr.open(uid, [1, 2, 3])
        sched.add(uid, priority=prio)
    order = [seq.uid for seq, _ in sched.next_schedule()]
    assert order == [2, 3, 1]
    for uid in (1, 2, 3):
        sched.retire(uid)
        mgr.flush(uid)
    # front=True (preempted requeue) beats FIFO within a priority class
    mgr.open(4, [7, 8])
    sched.add(4, priority=0)
    mgr.open(5, [9])
    sched.add(5, priority=0, front=True)
    order = [seq.uid for seq, _ in sched.next_schedule()]
    assert order == [5, 4]


def test_loop_crash_fails_streams_and_sheds_new_load(monkeypatch):
    """An engine failure must terminate every waiting stream with a typed
    error AND close the queue — a dead server accepting submits would
    park their result() calls forever."""
    model, eng = _tiny_engine()
    srv = InferenceServer(eng).start()
    [p] = _prompts(model, (4,))

    def boom(*a, **k):
        raise RuntimeError("injected engine failure")

    monkeypatch.setattr(eng, "launch", boom)   # the greedy loop's call
    s = srv.submit(p, SamplingParams(max_new_tokens=4))
    with pytest.raises(ServingError, match="serve loop died"):
        s.result(timeout=60)
    with pytest.raises(QueueFull):     # admission closed by crash handler
        srv.submit(p)
    with pytest.raises(RuntimeError, match="serve loop died"):
        srv.stop()                     # surfaces the original failure


def test_stop_drain_fails_fast_on_dead_loop(monkeypatch):
    """A crashed loop must not make stop(drain=True) wait out the drain
    timeout: the crash handler can itself wedge on the broken engine
    (flush on inconsistent state), so stop() polls and raises the loop
    error as soon as it is recorded."""
    model, eng = _tiny_engine()
    srv = InferenceServer(eng).start()
    [p] = _prompts(model, (4,))
    release = threading.Event()

    def boom(*a, **k):
        raise RuntimeError("injected engine failure")

    def wedged_flush(uid):
        # the crash handler's flush hangs on the broken engine — exactly
        # the state stop() must not wait out
        release.wait(30)

    monkeypatch.setattr(eng, "launch", boom)   # the greedy loop's call
    monkeypatch.setattr(eng, "flush", wedged_flush)
    srv.submit(p, SamplingParams(max_new_tokens=4))
    deadline = time.monotonic() + 10
    while srv._loop_error is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert srv._loop_error is not None
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="serve loop died"):
        srv.stop(drain=True, timeout=60)
    # fail-fast: seconds (the 1s handler grace), not the 60s drain wait
    assert time.monotonic() - t0 < 5.0
    release.set()


def test_metrics_monitor_export():
    """ServingMetrics events flow through a MonitorMaster-shaped sink."""
    class Sink:
        def __init__(self):
            self.events = []

        def write_events(self, evs):
            self.events.extend(evs)

    model, eng = _tiny_engine()
    [p] = _prompts(model, (4,))
    sink = Sink()
    srv = InferenceServer(eng, monitor=sink).start()
    try:
        srv.submit(p, SamplingParams(max_new_tokens=3)).result(timeout=120)
    finally:
        srv.stop()
    tags = {t for t, _v, _s in sink.events}
    assert {"serving/tokens_out", "serving/ttft_p50",
            "serving/tpot_p50", "serving/preemptions"} <= tags
    m = ServingMetrics()
    m.record_tokens(5)
    assert m.snapshot()["tokens_out"] == 5
