"""The spans and names ISSUE 26 added to the program: the serve loop's and
the ragged step's host spans, the train engine's hub-less tracer, the
clock anchor a profiler capture carries, and the jitted steps' names."""

import glob
import threading

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import step_counts
from deepspeed_tpu.serving import InferenceServer, SamplingParams
from deepspeed_tpu.telemetry.tracing import NULL_TRACER, SPAN_NAMES

NEW_SPANS = ("serve.admit_pass", "serve.deliver", "serve.idle_wait",
             "v2.schedule", "v2.h2d", "v2.dispatch", "v2.fetch")


def _tiny_engine(budget=16):
    from deepspeed_tpu.inference.v2 import build_engine
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("llama-tiny", num_layers=1)
    eng = build_engine(
        model, {"dtype": "float32",
                "state_manager": {"max_tracked_sequences": 8,
                                  "max_ragged_batch_size": budget},
                "memory_config": {"num_blocks": 64, "block_size": 4},
                "max_context": 64}, seed=0)
    return model, eng


def _serve(eng, model, config):
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, model.vocab_size, size=n).tolist()
               for n in (5, 20, 3)]
    srv = InferenceServer(eng, config).start()
    try:
        outs = {}

        def run(i):
            outs[i] = list(srv.submit(prompts[i],
                                      SamplingParams(max_new_tokens=5)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        srv.stop()
    assert all(len(outs[i]) == 5 for i in range(len(prompts)))
    return srv


# -- the count behind the v2.schedule span ---------------------------------
@pytest.mark.parametrize("window,query_block,want", [
    # A: 5 cached + 3 new (positions 5, 6, 7 see 6, 7, 8 keys);
    # B: 9 cached + 1 new (position 9 sees 10 keys); A's three rows share
    # a walk where the step runs the query-blocked kernel
    (None, 32, {"seqs": 2, "tokens": 4, "prefill_tokens": 3,
                "decode_tokens": 1, "blocked_rows": 3, "one_row_walks": 1,
                "kv_rows": 8 + 10,
                "qk_pairs": (6 + 7 + 8) + 10}),
    # window 7: A's positions see 6, 7, 7; B's sees 7; each context 7 rows;
    # a block of two rows cuts A's run into two rows and one
    (7, 2, {"seqs": 2, "tokens": 4, "prefill_tokens": 3, "decode_tokens": 1,
            "blocked_rows": 2, "one_row_walks": 2, "kv_rows": 7 + 7,
            "qk_pairs": (6 + 7 + 7) + 7}),
    # a window no context reaches changes nothing; no blocked kernel
    (64, 0, {"seqs": 2, "tokens": 4, "prefill_tokens": 3, "decode_tokens": 1,
             "blocked_rows": 0, "one_row_walks": 0, "kv_rows": 18,
             "qk_pairs": 31}),
])
def test_step_counts_hand_worked(window, query_block, want):
    assert step_counts([(5, 3), (9, 1)], window, query_block) == want


def test_step_counts_against_a_loop():
    rng = np.random.default_rng(0)
    for _ in range(50):
        items = [(int(rng.integers(0, 40)), int(rng.integers(1, 20)))
                 for _ in range(int(rng.integers(1, 6)))]
        window = int(rng.integers(1, 50)) if rng.random() < 0.7 else None
        pairs = sum(min(p + 1, window) if window else p + 1
                    for c, n in items for p in range(c, c + n))
        rows = sum(min(c + n, window) if window else c + n
                   for c, n in items)
        got = step_counts(items, window)
        assert (got["qk_pairs"], got["kv_rows"]) == (pairs, rows)
        # a first prompt token is prefill even when it comes alone
        assert got["decode_tokens"] == sum(1 for c, n in items
                                           if n == 1 and c > 0)


# -- the serve loop's and the ragged step's spans ---------------------------
def test_new_spans_present_with_arguments_and_parents():
    model, eng = _tiny_engine()
    srv = _serve(eng, model, {"tracing": {"enabled": True}})
    events = [e for e in srv.tracer.snapshot() if e["ph"] == "X"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert set(NEW_SPANS) <= set(by_name), sorted(by_name)
    assert set(by_name) <= set(SPAN_NAMES)

    ragged = {e["args"]["span_id"]: e for e in by_name["v2.ragged_step"]}
    for name in ("v2.schedule", "v2.h2d", "v2.dispatch", "v2.fetch"):
        for e in by_name[name]:
            parent = ragged[e["args"]["parent_id"]]      # KeyError = orphan
            assert parent["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1
    # every ragged step span ends with its ONE fetch, after whole launches
    # in order: the step's own (nothing ran ahead), the next step's (the
    # loop runs one ahead), both (the first of a run ahead) or none (the
    # last of one); a launch and a fetch a device step in all
    launch = ["v2.schedule", "v2.h2d", "v2.dispatch"]
    launches = 0
    for sid, parent in ragged.items():
        kids = [k["name"] for k in sorted(
            (e for n in launch + ["v2.fetch"] for e in by_name[n]
             if e["args"]["parent_id"] == sid), key=lambda e: e["ts"])]
        assert kids[-1] == "v2.fetch" and len(kids) % 3 == 1
        assert kids[:-1] == launch * (len(kids) // 3) and len(kids) <= 7
        launches += len(kids) // 3
    assert launches == len(ragged) == len(by_name["serve.step"])

    sched = by_name["v2.schedule"]
    for e in sched:
        a = e["args"]
        assert a["tokens"] == a["prefill_tokens"] + a["decode_tokens"]
        assert a["qk_pairs"] >= a["tokens"] and a["kv_rows"] >= a["seqs"]
        # llama-tiny's head of 32 rides the XLA gather path: no shared walk
        assert a["blocked_rows"] == 0
    # the 20-token prompt is split by the 16-token budget: prefill in
    # several steps; 5 new tokens a request: decode tokens in many
    assert sum(e["args"]["prefill_tokens"] for e in sched) == 5 + 20 + 3
    assert sum(e["args"]["decode_tokens"] for e in sched) == 3 * 4
    # one packed index buffer a step; every request greedy: one program
    for e in by_name["v2.h2d"]:
        assert e["args"]["arrays"] == 1 and e["args"]["bytes"] > 0
    assert all(e["args"]["programs"] == 1 for e in by_name["v2.dispatch"])
    shapes = [(e["args"]["t_bucket"], e["args"]["nb_bucket"],
               e["args"]["new_shape"]) for e in by_name["v2.dispatch"]]
    first = {}
    for t, nb, new in shapes:       # true exactly on a key's first use
        assert new == ((t, nb) not in first)
        first[(t, nb)] = True
    assert sum(e["args"]["tokens"] for e in by_name["serve.deliver"]) == 15
    assert sum(e["args"]["finished"] for e in by_name["serve.deliver"]) == 3
    assert sum(e["args"]["admitted"]
               for e in by_name["serve.admit_pass"]) == 3
    # admit_pass, step and deliver tile the loop: a deliver follows each
    # step that ran, and nothing of the loop's overlaps
    loop = sorted((e for n in ("serve.admit_pass", "serve.step",
                               "serve.deliver", "serve.idle_wait")
                   for e in by_name[n]), key=lambda e: e["ts"])
    for a, b in zip(loop, loop[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1
        if a["name"] == "serve.step" and "kv_exhausted" not in a["args"]:
            assert b["name"] == "serve.deliver"


def test_no_span_with_tracing_off():
    model, eng = _tiny_engine()
    srv = _serve(eng, model, {})
    assert not srv.tracer.enabled and srv.tracer.snapshot() == []
    assert eng._step_span is None
    # standalone engine, no tracer at all: step() and put() run as before
    model, eng = _tiny_engine()
    eng.admit(1, [3, 4, 5])
    assert list(eng.step()) == [1] and eng.tracer is None


def test_engine_step_spans_without_a_server():
    """``step()`` under a tracer of the caller's: the children hang off
    ``v2.ragged_step``; ``put()`` (no parent span) records roots."""
    from deepspeed_tpu.telemetry.tracing import Tracer

    model, eng = _tiny_engine()
    eng.tracer = Tracer()
    eng.admit(1, [3, 4, 5])
    eng.step()
    eng.put([2], [[7, 8]])
    names = [e["name"] for e in eng.tracer.snapshot()]
    assert names == ["v2.schedule", "v2.h2d", "v2.dispatch", "v2.fetch",
                     "v2.ragged_step", "v2.schedule", "v2.h2d",
                     "v2.dispatch"]
    roots = [e for e in eng.tracer.snapshot()[5:]]
    assert all("parent_id" not in e["args"] for e in roots)


def test_jitted_steps_carry_their_names():
    model, eng = _tiny_engine()
    for phase, want in (("decode", "jit_ragged_step"),
                        ("verify", "jit_ragged_verify")):
        fn, args = eng.audit_step_args(phase)
        assert f"module @{want} " in fn.lower(*args).as_text()
    for fn, want in ((eng._step_sampled, "ragged_step_sampled"),
                     (eng._decode_loop, "ragged_decode_loop"),
                     (eng._kv_write, "kv_write")):
        assert fn.__wrapped__.__name__ == want


# -- the train engine's tracer without the hub ------------------------------
def test_train_engine_tracing_alone_builds_no_hub(tmp_path):
    import json

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    trace_path = str(tmp_path / "train.trace.json")
    model = get_model_config("gpt2-tiny")
    config = {"train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "steps_per_print": 10_000,
              "telemetry": {"tracing": {"enabled": True,
                                        "trace_path": trace_path}}}
    engine, _, _, _ = ds.initialize(model=model, config=config)
    assert engine.telemetry is None and engine._watchdog is None
    assert engine.tracer.enabled and engine.tracer is not NULL_TRACER
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, size=(8, 33), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    for _ in range(3):
        engine.train_batch(batch)
    events = engine.tracer.snapshot()
    steps = [e for e in events if e["name"] == "train.step"]
    assert [e["args"]["step"] for e in steps] == [1, 2, 3]
    # no hub: no sync and no record, so neither span exists
    assert {e["name"] for e in events} == {"train.step", "train.data_ingest",
                                           "train.dispatch"}
    for e in events:
        if e["name"] != "train.step":
            assert e["args"]["parent_id"] in {s["args"]["span_id"]
                                              for s in steps}
    engine.destroy()
    with open(trace_path) as f:
        exported = [e for e in json.load(f)["traceEvents"]
                    if e["ph"] == "X"]
    assert len(exported) == len(events)


@pytest.mark.parametrize("seq,on_chip,want", [
    (1024, True, "plan"),      # the repo kernels run: what plan() executes
    (1024, False, None),       # off the TPU attention is the XLA path
])
def test_first_train_step_span_carries_flash_executed_shares(
        monkeypatch, seq, on_chip, want):
    """A run's first ``train.step`` span says what share of S² the flash
    kernels execute for the batch's length, by the rule the kernels
    themselves are chosen by; later steps and other attention paths say
    nothing."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.ops.pallas.flash_mha import plan

    model = get_model_config("gpt2-tiny")
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
        "telemetry": {"tracing": {"enabled": True}}})
    monkeypatch.setattr(fa, "on_tpu", lambda: on_chip)
    got = engine._flash_executed_shares(
        {"input_ids": np.zeros((8, seq), np.int32)})
    if want is None:
        assert got == {}
    else:
        p = plan(seq, model.dim_per_head, 1, True, None)
        assert got == {
            "flash_executed_share_fwd": round(p.executed_share_fwd, 4),
            "flash_executed_share_bwd": round(p.executed_share_bwd, 4)}
        assert 0.5 < got["flash_executed_share_fwd"] <= 0.65
    assert engine._flash_executed_shares([1, 2]) == {}     # no token ids
    # on the spans: the first step's only (the count is stubbed, so the
    # step itself runs the CPU's XLA attention)
    monkeypatch.setattr(fa, "on_tpu", lambda: False)
    monkeypatch.setattr(fa, "flash_executed_shares",
                        lambda *a, **k: (0.625, 0.6))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, size=(8, 33), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    for _ in range(2):
        engine.train_batch(batch)
    first, second = [e["args"] for e in engine.tracer.snapshot()
                     if e["name"] == "train.step"]
    assert (first["flash_executed_share_fwd"],
            first["flash_executed_share_bwd"]) == (0.625, 0.6)
    assert "flash_executed_share_fwd" not in second
    engine.destroy()


def test_train_engine_without_tracing_shares_the_null_tracer():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import get_model_config

    engine, _, _, _ = ds.initialize(
        model=get_model_config("gpt2-tiny"),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    assert engine.tracer is NULL_TRACER and engine.telemetry is None
    engine.destroy()


# -- the clock anchor --------------------------------------------------------
def _anchors(logdir):
    from jax.profiler import ProfileData

    from deepspeed_tpu.utils.trace import CLOCK_ANCHOR

    pb = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    return [(ev.start_ns, dict(ev.stats))
            for plane in ProfileData.from_file(pb).planes
            for line in plane.lines for ev in line.events
            if ev.name == CLOCK_ANCHOR]


def test_clock_anchor_lands_in_a_capture(tmp_path):
    import time

    import jax

    from deepspeed_tpu.utils.trace import write_clock_anchor

    assert write_clock_anchor("no capture") > 0      # free, and no error
    jax.profiler.start_trace(str(tmp_path))
    t0 = write_clock_anchor("start")
    time.sleep(0.05)
    t1 = write_clock_anchor("stop")
    jax.profiler.stop_trace()
    found = sorted(_anchors(tmp_path))
    assert [s["label"] for _, s in found] == ["start", "stop"]
    assert [s["monotonic_ns"] for _, s in found] == [t0, t1]
    # both clocks tick alike: 50 ms apart on either, within 1 ms
    assert abs((found[1][0] - found[0][0]) - (t1 - t0)) < 1e6


def test_trace_profiler_writes_both_anchors(tmp_path):
    from deepspeed_tpu.utils.trace import TraceProfiler

    prof = TraceProfiler(str(tmp_path), start_step=1, num_steps=1)
    prof.maybe_start(1)
    assert prof.active
    with prof.step(1):
        pass
    prof.maybe_stop(2)
    assert prof.done and not prof.active
    assert [s["label"] for _, s in sorted(_anchors(tmp_path))] == ["start",
                                                                   "stop"]
