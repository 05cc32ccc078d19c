"""The serve loop one step ahead (serving/server.py ``_step_once``): step
N+1's program is called before step N's tokens reach the host, the sampled
tokens go from one to the other on the device, and everything but the
plain greedy path fetches the running step first.  CPU, tiny models: what
is held here is that the streams are plain greedy decoding's token for
token and that engine, allocator and slots end as a loop that never ran
ahead leaves them; how long anything takes is the chip's to say.
"""

import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.serving import (DeadlineExceeded, InferenceServer,
                                   RequestCancelled, SamplingParams)

# preset -> engine configuration beside the common keys: a plain GQA model,
# one with a recurrent slot a sequence, one whose window layers free pages
# behind the window (prompts past its window of 24), a latent one, and
# that one drafting for itself (its greedy step is the drafting program)
DRAFTING = "glm-5-tiny:self_draft"
_MODELS = {
    "llama-tiny": {},
    "falcon-h1-tiny": {},
    "trinity-tiny": {"memory_config": {"num_blocks": 64, "block_size": 8,
                                       "window_blocks": 32}},
    "glm-5-tiny": {},
    DRAFTING: {"self_draft": True},
}


def _engine(name, seed=0, **kw):
    model = get_model_config(name.split(":")[0])
    cfg = {"dtype": "float32", "max_context": 160,
           "memory_config": {"num_blocks": 64, "block_size": 8},
           "state_manager": {"max_tracked_sequences": 4,
                             "max_ragged_batch_size": 16}}
    cfg.update(_MODELS.get(name, {}))
    cfg.update(kw)
    return model, InferenceEngineV2(model, cfg, seed=seed)


def _prompts(model, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, model.vocab_size, size=n).tolist() for n in sizes]


def _reference(name, prompts, new, **kw):
    """Each prompt alone through the synchronous engine: greedy streams
    depend on nothing but the prompt (nor on who drafts)."""
    _, eng = _engine(name.split(":")[0], **kw)
    return [eng.generate([p], max_new_tokens=n)[0]
            for p, n in zip(prompts, new)]


def _arrive_in_flight(srv, later):
    """Submit ``later``'s requests one a hold, from inside the hold: each
    arrives while a step is on the chip, and the loop admits it into the
    step it is about to launch.  Returns the list their streams land in."""
    streams, hold = [], srv._hold

    def holding(go):
        if later:
            prompt, n = later.pop(0)
            streams.append(srv.submit(prompt,
                                      SamplingParams(max_new_tokens=n)))
        return hold(go)

    srv._hold = holding
    return streams


class _Known(dict):
    """The loop's table of program times with a time for every program:
    no step is fetched before the next is launched for want of one, so
    what a test does to a running loop finds a step on the chip."""

    def get(self, key, default=None):
        return super().get(key, 1e-3)


def _warm(eng, prompts, new):
    """Compile what serving ``prompts`` will run, through the calls the
    benchmark's warm-up makes, so that a deadline counts steps and not
    compiles."""
    left = {}
    for i, p in enumerate(prompts):
        eng.admit((1 << 20) + i, p)
        left[(1 << 20) + i] = new
    while left:
        for uid, tok in eng.step(temperature=0.0).items():
            left[uid] -= 1
            if left[uid]:
                eng.extend(uid, tok)
            else:
                eng.flush(uid)
                del left[uid]


def _assert_clean(eng):
    mgr = eng.state_manager
    assert mgr.n_active == 0 and not eng.scheduler.has_work
    assert eng.free_blocks == eng.cfg.num_blocks - 1
    assert sorted(mgr._free_slots) == list(range(mgr.max_seqs))
    if mgr.window:
        assert eng.free_window_blocks == eng.cfg.window_blocks - 1
    assert eng._flight is None


# -- streams ------------------------------------------------------------------
@pytest.mark.parametrize("name", list(_MODELS))
def test_streams_equal_the_synchronous_engines(name):
    model, eng = _engine(name)
    sizes, new = (5, 37, 12, 50, 9, 30), (20, 9, 14, 6, 11, 1)
    prompts = _prompts(model, sizes)
    ref = _reference(name, prompts, new)
    srv = InferenceServer(eng)
    later = list(zip(prompts[2:], new[2:]))
    arrived = _arrive_in_flight(srv, later)
    srv.start()
    try:
        first = [srv.submit(p, SamplingParams(max_new_tokens=n))
                 for p, n in zip(prompts[:2], new[:2])]
        outs = [s.result(timeout=300) for s in first]
        deadline = time.monotonic() + 300
        while later and time.monotonic() < deadline:
            time.sleep(0.01)
        outs += [s.result(timeout=300) for s in list(arrived)]
    finally:
        srv.stop()
    assert not later and len(arrived) == 4      # each arrived mid-flight
    assert outs == ref
    # every request got exactly what it asked for, the one-token one too
    assert [len(o) for o in outs] == list(new)
    snap = srv.metrics.snapshot()
    assert snap["steps_ahead"] > snap["steps"] // 2
    assert snap["tokens_out"] == sum(new)
    _assert_clean(eng)


@pytest.mark.parametrize("new", [1, 2, 3, 8])
def test_a_request_ending_by_max_new_tokens_gets_exactly_that_many(new):
    """Known without the token: such a sequence rides no further step, so
    the engine never runs a row for a token nobody asked for."""
    model, eng = _engine("llama-tiny")
    prompts = _prompts(model, (6, 6, 19), seed=new)
    ref = _reference("llama-tiny", prompts, (new, new + 5, new))
    rows = []
    launch = eng.launch

    def counting(*a, **kw):
        flight = launch(*a, **kw)
        if flight is not None:
            rows.append(len(flight.uids))
        return flight

    eng.launch = counting
    with InferenceServer(eng) as srv:
        streams = [srv.submit(p, SamplingParams(max_new_tokens=n))
                   for p, n in zip(prompts, (new, new + 5, new))]
        outs = [s.result(timeout=300) for s in streams]
    assert outs == ref
    # a sampled row a delivered token, none thrown away
    assert sum(rows) == 3 * new + 5 == srv.metrics.tokens_out
    _assert_clean(eng)


# -- an eos the host could not see ---------------------------------------------
@pytest.mark.parametrize("name", ["llama-tiny", "falcon-h1-tiny"])
def test_an_eos_mid_flight_rides_one_dead_row(name):
    model, eng = _engine(name)
    long_p, other_p, late_p = _prompts(model, (11, 7, 13), seed=3)
    ref_long, ref_other, ref_late = _reference(
        name, (long_p, other_p, late_p), (24, 24, 10))
    # the first token of the stream past its fourth that no earlier one
    # equals: the step after its step is on the chip when it reaches the host
    k = next(i for i in range(4, 24) if ref_long[i] not in ref_long[:i])
    eos = ref_long[k]
    srv = InferenceServer(eng)
    srv._device_s = _Known()
    flushed, late = [], []
    flush = srv._flush_seq

    def flushing(uid):
        ahead = srv._flight
        slot = eng.state_manager.get(uid).slot
        flushed.append((uid, slot,
                        ahead is not None and uid in ahead.step.uids))
        flush(uid)
        if uid == 0:
            # the pages and the slot go to a request that arrives now,
            # with the dead row still on the chip
            late.append(srv.submit(late_p, SamplingParams(max_new_tokens=10)))

    srv._flush_seq = flushing
    srv.start()
    try:
        s_long = srv.submit(long_p, SamplingParams(max_new_tokens=24,
                                                   eos_token_id=eos))
        s_other = srv.submit(other_p, SamplingParams(max_new_tokens=24))
        out_long = s_long.result(timeout=300)
        out_other = s_other.result(timeout=300)
        out_late = late[0].result(timeout=300)
        slot_late = None
    finally:
        srv.stop()
    # nothing past the eos, and its neighbours' streams untouched
    assert out_long == ref_long[:k + 1] and out_long[-1] == eos
    assert out_other == ref_other and out_late == ref_late
    # it rode the step launched before its eos was seen ...
    assert flushed[0][0] == 0 and flushed[0][2]
    # ... whose token for it was thrown away
    assert srv.metrics.tokens_out == k + 1 + 24 + 10
    # the late arrival took over its slot (and its recurrent state's)
    slot_late = next(slot for uid, slot, _ in flushed if uid == 2)
    assert slot_late == flushed[0][1]
    _assert_clean(eng)


def test_a_drafting_step_ahead_ends_its_streams_exactly():
    """Bursts of two with the next step already on the chip: an
    ``eos_token_id`` that is a burst's first token and one that is its
    second (nothing past either is delivered; the step launched
    meanwhile ran dead rows), and a ``max_new_tokens`` that a burst's
    first token meets (its second is cut)."""
    model, eng = _engine(DRAFTING)
    prompts = _prompts(model, (11, 7, 13), seed=3)
    ref = _reference(DRAFTING, prompts, (40, 40, 40))
    # how each stream falls into bursts: what the drafts come to depends
    # on nothing but the stream (the same engine, fetched step by step)
    firsts, seconds = {}, {}
    for i, p in enumerate(prompts):
        eng.admit(i, p)
    seen = [0] * 3
    while min(seen) < 40:
        for i, burst in eng.step_bursts().items():
            if len(burst) == 2:
                firsts.setdefault(i, []).append(seen[i])
                seconds.setdefault(i, []).append(seen[i] + 1)
            seen[i] += len(burst)
            eng.extend(i, burst[-1])
    for i in range(3):
        assert eng.state_manager.get(i).tokens[len(prompts[i]):][:40] \
            == ref[i]
        eng.flush(i)

    def fresh(i, at):       # a token no earlier one of the stream equals
        return next(k for k in at[i] if 4 <= k < 39
                    and ref[i][k] not in ref[i][:k])

    k0, k1 = fresh(0, firsts), fresh(1, seconds)
    k2 = max(k for k in firsts[2] if k < 39)
    srv = InferenceServer(eng)
    srv._device_s = _Known()
    with srv:
        streams = [
            srv.submit(prompts[0], SamplingParams(
                max_new_tokens=40, eos_token_id=ref[0][k0])),
            srv.submit(prompts[1], SamplingParams(
                max_new_tokens=40, eos_token_id=ref[1][k1])),
            srv.submit(prompts[2], SamplingParams(max_new_tokens=k2 + 1))]
        outs = [s.result(timeout=300) for s in streams]
    assert outs == [ref[0][:k0 + 1], ref[1][:k1 + 1], ref[2][:k2 + 1]]
    snap = srv.metrics.snapshot()
    assert snap["steps_ahead"] > snap["steps"] // 2
    assert snap["tokens_out"] == k0 + k1 + k2 + 3
    assert 0 < snap["spec_accepted"] < snap["spec_proposed"]
    _assert_clean(eng)


# -- whatever is not the plain path drains first -------------------------------
def _drains(srv):
    """Record, per ``_drain`` call and per launch that found the KV pool
    exhausted, whether a step was on the chip; hold every preemption to
    finding none."""
    from deepspeed_tpu.inference.v2.ragged import KVCacheExhausted

    eng = srv.engine
    seen, drain, launch, preempt = [], srv._drain, eng.launch, \
        srv._preempt_one

    def draining():
        seen.append(srv._flight is not None)
        drain()

    def launching(*a, **kw):
        try:
            return launch(*a, **kw)
        except KVCacheExhausted:
            seen.append(eng._flight is not None)
            raise

    def preempting():
        assert srv._flight is None and eng._flight is None
        preempt()

    srv._drain, eng.launch, srv._preempt_one = draining, launching, preempting
    return seen


@pytest.mark.parametrize("what", ["cancel", "deadline", "kv_exhausted",
                                  "low_watermark", "cancel:self_draft",
                                  "kv_exhausted:self_draft"])
def test_with_a_step_in_flight_the_rest_drains_first(what):
    what, _, drafting = what.partition(":")
    name = DRAFTING if drafting else "llama-tiny"
    tight = what in ("kv_exhausted", "low_watermark")
    kw = {}
    if tight:
        # 23 usable pages of 4: four 8-token prompts admit at 2 pages and
        # grow to 5 each, 20 of 23 with a fifth sequence's 2 on top
        kw = {"memory_config": {"num_blocks": 24, "block_size": 4},
              "max_context": 32,
              "state_manager": {"max_tracked_sequences": 8,
                                "max_ragged_batch_size": 32}}
    model, eng = _engine(name, **kw)
    # (the tiny latent model's gather wants a context bucket of whole
    # 128s: its streams, and the warm-up's of up to twice the tokens, stay
    # under the 16-page bucket)
    n_req, new = (8, 12) if tight else (3, 50 if drafting else 120)
    prompts = _prompts(model, [8] * n_req, seed=7)
    ref = _reference(name, prompts, [new] * n_req, **kw)
    if not tight:
        _warm(eng, prompts, new)
    config = {}
    if what == "low_watermark":
        config = {"admission": {"kv_low_watermark": 0.2,
                                "kv_high_watermark": 0.25}}
    srv = InferenceServer(eng, config)
    srv._device_s = _Known()
    seen = _drains(srv)
    srv.start()
    try:
        streams = [srv.submit(
            p, SamplingParams(max_new_tokens=new),
            deadline_s=600 if what == "deadline" and i == 0 else None)
            for i, p in enumerate(prompts)]
        if what in ("cancel", "deadline"):
            while len(streams[0].tokens) < 5:
                time.sleep(0.002)
            if what == "cancel":
                streams[0].cancel()
            else:       # its budget runs out now, mid-decode
                srv._active[streams[0].uid].deadline = time.monotonic()
        outs = []
        for i, s in enumerate(streams):
            if i == 0 and what in ("cancel", "deadline"):
                with pytest.raises(RequestCancelled if what == "cancel"
                                   else DeadlineExceeded):
                    s.result(timeout=300)
                # what it had delivered is a prefix of its stream
                assert s.tokens == ref[0][:len(s.tokens)]
                assert len(s.tokens) < new
                outs.append(ref[0])
            else:
                outs.append(s.result(timeout=300))
    finally:
        srv.stop()
    assert outs == ref
    assert any(seen), seen          # a drain found a step on the chip
    if tight:
        assert srv.metrics.preemptions >= 1
    assert srv.metrics.steps_ahead > 0
    _assert_clean(eng)


# -- what stays synchronous ------------------------------------------------------
@pytest.mark.parametrize("what", ["not_greedy", "self_draft"])
def test_the_synchronous_paths_launch_nothing_ahead(what):
    """A batch not all greedy samples on the host: nothing is launched
    behind such a step, whether or not the engine drafts for itself (its
    steps then run without the module, and its greedy stream is plain
    greedy decoding's all the same)."""
    name = DRAFTING if what == "self_draft" else "llama-tiny"
    model, eng = _engine(name)
    prompts = _prompts(model, (9, 21, 5), seed=11)
    params = SamplingParams(max_new_tokens=10, temperature=0.8)
    with InferenceServer(eng) as srv:
        # one greedy request beside the others: a batch not ALL greedy
        streams = [srv.submit(p, params) for p in prompts[:2]]
        streams.append(srv.submit(prompts[2],
                                  SamplingParams(max_new_tokens=10)))
        outs = [s.result(timeout=300) for s in streams]
    assert [len(o) for o in outs] == [10] * 3
    assert srv.metrics.steps_ahead == 0
    assert srv.metrics.arrivals_after_launch == 0
    if what == "self_draft":
        assert outs[2] == _reference(name, prompts[2:], [10])[0]
    _assert_clean(eng)


def test_a_greedy_tail_after_a_sampled_batch_runs_ahead_again():
    """What decides is the step the loop is about to run: once the last
    request that samples on the host is done, the greedy ones go on one
    step ahead, and their streams are what they would have been."""
    model, eng = _engine("llama-tiny")
    hot, cold = _prompts(model, (6, 15), seed=5)
    ref = _reference("llama-tiny", [cold], [40])[0]
    with InferenceServer(eng) as srv:
        s_hot = srv.submit(hot, SamplingParams(max_new_tokens=4,
                                               temperature=0.9))
        s_cold = srv.submit(cold, SamplingParams(max_new_tokens=40))
        assert len(s_hot.result(timeout=300)) == 4
        assert s_cold.result(timeout=300) == ref
    assert 0 < srv.metrics.steps_ahead < srv.metrics.steps
    _assert_clean(eng)


# -- one program a bucket, compiled by step() ------------------------------------
def test_step_compiles_the_programs_the_loop_runs():
    """A warm-up through ``step`` / ``extend`` / ``flush`` (the benchmark's)
    compiles every program the loop one step ahead then runs: the fed-back
    operand is the same array whoever calls, zeros before any step."""
    model, eng = _engine("llama-tiny")
    prompt, = _prompts(model, (21,), seed=2)
    compiles = []

    def on_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        eng.admit(1 << 20, prompt)
        out = []
        while len(out) < 30:
            toks = eng.step(temperature=0.0)
            if 1 << 20 in toks:
                out.append(toks[1 << 20])
                eng.extend(1 << 20, out[-1])
        eng.flush(1 << 20)
        warm = set(eng._dispatched)
        assert compiles and eng._step_sampled._cache_size() == len(warm)
        del compiles[:]
        with InferenceServer(eng) as srv:
            served = srv.submit(
                prompt, SamplingParams(max_new_tokens=30)).result(timeout=300)
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(on_compile)
    assert served == out
    assert srv.metrics.steps_ahead > 0
    assert not compiles                     # none in the served window
    assert eng._dispatched == warm          # one program a bucket, as before
    assert eng._step_sampled._cache_size() == len(warm)


# -- the spans --------------------------------------------------------------------
def test_the_spans_of_a_run_ahead_and_the_benchmarks_readers():
    from benchmark.readers import host, spans

    model, eng = _engine("llama-tiny")
    prompts = _prompts(model, (9, 33), seed=4)
    srv = InferenceServer(eng, {"tracing": {"enabled": True}})
    t0 = time.monotonic()
    with srv:
        outs = [s.result(timeout=300) for s in
                [srv.submit(p, SamplingParams(max_new_tokens=25))
                 for p in prompts]]
    t1 = time.monotonic()
    assert [len(o) for o in outs] == [25, 25]
    events = [e for e in srv.tracer.snapshot() if e["ph"] == "X"]
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    steps = srv.metrics.steps
    # one of each a device step
    for name in ("serve.step", "serve.deliver", "v2.ragged_step",
                 "v2.schedule", "v2.h2d", "v2.dispatch", "v2.fetch"):
        assert len(by[name]) == steps, (name, len(by[name]), steps)
    ahead = [e["args"]["ahead"] for e in by["v2.dispatch"]]
    assert set(ahead) == {0, 1} and sum(ahead) == srv.metrics.steps_ahead
    for e in by["v2.dispatch"]:
        assert e["args"]["programs"] == 1
    for e in by["v2.h2d"]:
        assert e["args"]["arrays"] == 1
    # every serve.step holds one ragged step span, which ends in its fetch
    ragged = sorted(by["v2.ragged_step"], key=lambda e: e["ts"])
    for outer, inner, fetch in zip(
            sorted(by["serve.step"], key=lambda e: e["ts"]), ragged,
            sorted(by["v2.fetch"], key=lambda e: e["ts"])):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        assert fetch["args"]["parent_id"] == inner["args"]["span_id"]
        assert "held_us" in outer["args"] and outer["args"]["held_us"] >= 0
    # a step launched ahead is dispatched before the fetch in its span,
    # so the fetch waits for the step BEFORE the one just launched
    for e in by["v2.dispatch"]:
        if e["args"]["ahead"]:
            fetch = next(f for f in by["v2.fetch"]
                         if f["args"]["parent_id"] == e["args"]["parent_id"])
            assert e["ts"] + e["dur"] <= fetch["ts"] + 1

    # the benchmark's readers still find what they read
    class Run:
        counters = {"window_mono_us": (t0 * 1e6, t1 * 1e6)}

    run = Run()
    run.spans = srv.tracer.snapshot()
    per_step = spans.serve_host_ms([e for e in events])
    assert len(per_step) == steps and all(ms >= 0 for ms in per_step)
    assert spans.serve_host_ms_p50(run, None) > 0
    assert host.serve_step_ms_p50(run, None) > 0
    assert host.queue_wait_p50_ms(run, None) >= 0
    assert host.prefill_tokens_per_s(run, None) > 0
