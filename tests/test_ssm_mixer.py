"""A Mamba-2 mixer beside attention (Falcon-H1) on the serving path: the
segment-aware scan against a plain recurrence, the Pallas kernel
(interpreted) against the XLA scan, ``falcon-h1-tiny`` through
``InferenceEngineV2`` against the benchmark's plain reference, the state
slots' life (reuse, preemption), and every path that would need a
snapshot of the state refusing by name."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.engine_v2 import (RecurrentStateUnsupported,
                                                  ssm_step_counts)
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.models import transformer as tf_model
from deepspeed_tpu.ops.pallas import ssd_ragged as sr

reference = importlib.import_module("benchmark.reference.falcon_h1")

H, P, N, G, S = 4, 32, 16, 2, 6          # heads, head dim, state, groups, slots


@pytest.fixture
def interpret():
    old, sr.INTERPRET = sr.INTERPRET, True
    yield
    sr.INTERPRET = old


def recurrence(x, dt, a, b, c, state, slot, pos):
    """The scan as the sentence says it: row by row, in float64."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    st = np.asarray(state, np.float64).copy()
    t, heads, _ = x.shape
    per = heads // b.shape[1]
    y = np.zeros(x.shape)
    for i in range(t):
        s = slot[i]
        if i == 0 or slot[i - 1] != s:
            cur = np.zeros_like(st[s]) if pos[i] == 0 else st[s].copy()
        for h in range(heads):
            cur[h] = np.exp(dt[i, h] * a[h]) * cur[h] \
                + dt[i, h] * np.outer(x[i, h], b[i, h // per])
            y[i, h] = cur[h] @ c[i, h // per]
        if i == t - 1 or slot[i + 1] != s:
            st[s] = cur
    return y, st


# rows of a step: (slot, first position, rows) runs, then a pad tail
LAYOUTS = {
    # three decode rows, a prompt from position 0 in a slot that holds
    # another sequence's old state, a chunk continuing at 7 that ends
    # mid-chunk, then padding
    "mixed": ([(2, 4, 1), (0, 9, 1), (5, 1, 1), (1, 0, 11), (3, 7, 5)], 5),
    "runs_of_one": ([(0, 3, 1), (1, 0, 1), (2, 5, 1), (3, 1, 1), (4, 0, 1),
                     (5, 2, 1)], 0),
    "one_long_run": ([(4, 10, 32)], 0),
    "all_from_zero": ([(1, 0, 3), (2, 0, 9), (0, 0, 1)], 3),
}


def _case(name, seed=0):
    runs, n_pad = LAYOUTS[name]
    slot = np.concatenate([np.full(n, s) for s, _, n in runs]
                          + [np.full(n_pad, S)]).astype(np.int32)
    pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs]
                         + [np.zeros(n_pad)]).astype(np.int32)
    rng = np.random.default_rng(seed)
    t = len(slot)
    f = np.float32
    return dict(
        x=rng.standard_normal((t, H, P)).astype(f),
        dt=(0.3 * np.log1p(np.exp(rng.standard_normal((t, H))))).astype(f),
        a=-np.exp(rng.uniform(0, 1.5, H)).astype(f),
        b=rng.standard_normal((t, G, N)).astype(f),
        c=rng.standard_normal((t, G, N)).astype(f),
        state=rng.standard_normal((S + 1, H, P, N)).astype(f),
        slot=slot, pos=pos)


def _args(k):
    return (k["x"], k["dt"], k["a"], k["b"], k["c"], jnp.asarray(k["state"]),
            k["slot"], k["pos"])


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_xla_scan_is_the_recurrence(layout):
    k = _case(layout)
    want_y, want_s = recurrence(*(k[n] for n in
                                  ("x", "dt", "a", "b", "c", "state", "slot",
                                   "pos")))
    y, s = sr.ssd_ragged(*_args(k), impl="xla")
    real = k["slot"] != S
    np.testing.assert_allclose(np.asarray(y)[real], want_y[real], atol=2e-5)
    np.testing.assert_allclose(np.asarray(s)[:S], want_s[:S], atol=2e-5)


@pytest.mark.parametrize("chunk", [8, 16, 128])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_is_the_xla_scan(layout, chunk, interpret):
    """Chunks of 8 cut the 11-row prompt and the 32-row run; 16 ends a run
    mid-chunk; 128 pads the whole step into one chunk."""
    k = _case(layout, seed=1)
    want_y, want_s = sr.ssd_ragged(*_args(k), impl="xla")
    y, s = sr.ssd_ragged(*_args(k), impl="pallas", chunk=chunk)
    real = k["slot"] != S
    np.testing.assert_allclose(np.asarray(y)[real],
                               np.asarray(want_y)[real], atol=2e-5)
    # the pad slot is the XLA scan's garbage row and not the kernel's
    np.testing.assert_allclose(np.asarray(s)[:S], np.asarray(want_s)[:S],
                               atol=2e-5)
    touched = {s_ for s_, _, _ in LAYOUTS[layout][0]}
    for idle in set(range(S)) - touched:
        np.testing.assert_array_equal(np.asarray(s)[idle], k["state"][idle])


def test_piece_tables_cut_runs_at_chunks():
    slot = jnp.asarray([2, 0] + [1] * 11 + [S] * 3, jnp.int32)
    pos = jnp.asarray([4, 9] + list(range(11)) + [0] * 3, jnp.int32)
    chunk, r0, r1, in_slot, out_slot, flags = (
        np.asarray(v) for v in sr.piece_tables(slot, pos, 8, 10, S))
    # rows 0, 1 | 2..7 of the prompt | 8..12 of it | 13..15 padding
    assert list(zip(chunk[:5], r0[:5], r1[:5])) == [
        (0, 0, 1), (0, 1, 2), (0, 2, 8), (1, 0, 5), (1, 5, 8)]
    assert list(out_slot[:5]) == [2, 0, 1, 1, 1]       # the pad piece keeps 1
    # the prompt starts from zeros and reads no block; its second piece
    # goes on from the carry: both name the block before them
    assert list(in_slot[:4]) == [2, 0, 0, 0]
    assert [int(f) for f in flags[:5]] == [
        sr._FIRST_OF_RUN | sr._FIRST_IN_CHUNK, sr._FIRST_OF_RUN,
        sr._FIRST_OF_RUN | sr._ZERO, sr._FIRST_IN_CHUNK, sr._SKIP]
    assert all(f == sr._SKIP for f in flags[5:])


# -- the model through the engine -------------------------------------------
def hf_config(model):
    """The tiny preset in the published file's keys, for the reference."""
    m = model.ssm
    return {
        "num_hidden_layers": model.num_layers,
        "hidden_size": model.hidden_size,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads,
        "rms_norm_eps": model.layernorm_eps, "rope_theta": int(model.rope_theta),
        "mamba_d_ssm": m.d_ssm, "mamba_expand": 2,
        "mamba_n_heads": m.num_heads, "mamba_d_state": m.state_size,
        "mamba_n_groups": m.n_groups, "mamba_d_conv": m.conv_kernel,
        "mamba_conv_bias": m.conv_bias, "mamba_rms_norm": True,
        "mamba_norm_before_gate": False,
        "embedding_multiplier": m.embedding_multiplier,
        "lm_head_multiplier": m.lm_head_multiplier,
        "attention_in_multiplier": m.attention_in_multiplier,
        "attention_out_multiplier": m.attention_out_multiplier,
        "key_multiplier": m.key_multiplier,
        "ssm_in_multiplier": m.ssm_in_multiplier,
        "ssm_out_multiplier": m.ssm_out_multiplier,
        "ssm_multipliers": list(m.ssm_multipliers),
        "mlp_multipliers": list(m.mlp_multipliers)}


ENGINE = {"dtype": "float32",
          "memory_config": {"num_blocks": 64, "block_size": 8},
          "max_context": 128,
          "state_manager": {"max_tracked_sequences": 4,
                            "max_ragged_batch_size": 16}}


def tiny_engine(seed=3, **engine):
    model = get_model_config("falcon-h1-tiny")
    return model, InferenceEngineV2(model, dict(ENGINE, **engine), seed=seed)


def reference_rows(eng, model, tokens, last):
    return np.asarray(reference.logits(
        eng.params, np.asarray([tokens]), hf_config(model),
        jax.devices()[0], last=last))[0]


def test_preset_holds_the_published_sizes():
    m = get_model_config("falcon-h1-34b")
    assert (m.hidden_size, m.num_layers, m.num_heads, m.kv_heads,
            m.dim_per_head, m.intermediate_size, m.vocab_size) == (
                5120, 72, 20, 4, 128, 21504, 261120)
    s = m.ssm
    assert (s.num_heads, s.head_dim, s.state_size, s.n_groups, s.conv_kernel,
            s.chunk_size, s.d_ssm, s.conv_dim, s.proj_dim) == (
                32, 128, 256, 2, 4, 128, 4096, 5120, 9248)
    assert (s.embedding_multiplier, s.lm_head_multiplier, s.key_multiplier,
            s.attention_out_multiplier, s.ssm_out_multiplier) == (
                5.656854249492381, 0.0078125, 0.011048543456039804, 0.0375,
                0.08838834764831845)
    assert m.rope_theta == 1e11 and not m.tie_embeddings
    assert all(get_model_config(n).ssm is None
               for n in ("mistral-7b", "gpt2-350m", "opt-1.3b"))
    tiny = get_model_config("falcon-h1-tiny").ssm
    mults = (tiny.embedding_multiplier, tiny.lm_head_multiplier,
             tiny.attention_in_multiplier, tiny.attention_out_multiplier,
             tiny.key_multiplier, tiny.ssm_in_multiplier,
             tiny.ssm_out_multiplier, *tiny.ssm_multipliers,
             *tiny.mlp_multipliers)
    assert all(v != 1.0 for v in mults)


def test_engine_matches_the_reference_across_chunks_and_decode():
    """A 45-token prompt through a 16-token step budget (three chunks, the
    convolution's tail and the state handed over through the slot twice)
    beside another sequence's decode rows, then decoded through the
    slot.  Both sides are float32 under "highest" matmul precision on one
    set of weights; what is left is the order of the sums (the chunked
    scan against the recurrence, paged against dense attention)."""
    model, eng = tiny_engine()
    rng = np.random.default_rng(0)
    a, b = 11, 12
    stream_a = rng.integers(0, model.vocab_size, 5).tolist()
    stream_b = rng.integers(0, model.vocab_size, 45).tolist()
    rows_a, rows_b = [], []          # (tokens so far, logits after them)

    out = eng.put([a], [stream_a])
    rows_a.append((list(stream_a), out[a]))
    first = True
    while len(rows_b) < 4:
        stream_a.append(int(rows_a[-1][1].argmax()))
        eng.extend(a, stream_a[-1])
        if rows_b:
            stream_b.append(int(rows_b[-1][1].argmax()))
            eng.extend(b, stream_b[-1])
        out = eng.put([b], [stream_b]) if first else eng.put([], [])
        first = False
        rows_a.append((list(stream_a), out[a]))
        if b in out:
            rows_b.append((list(stream_b), out[b]))
    assert len(rows_a) >= 6            # b's chunks ran beside a's rows

    for stream, rows in ((stream_a, rows_a), (stream_b, rows_b)):
        want = reference_rows(eng, model, rows[-1][0], last=len(rows))
        got = np.stack([r for _, r in rows])
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_generate_runs_the_fused_decode_loop_with_state():
    """``generate`` prefill steps, then ``ragged_decode_loop``: the same
    tokens as one ``put`` at a time."""
    model, eng = tiny_engine()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, model.vocab_size, n).tolist()
               for n in (20, 7)]
    got = eng.generate(prompts, max_new_tokens=6)
    _, one = tiny_engine()
    for uid, prompt in enumerate(prompts):
        toks, out = [], one.put([uid], [prompt])
        while uid not in out:
            out = one.put([], [])
        for _ in range(6):
            toks.append(int(out[uid].argmax()))
            one.extend(uid, toks[-1])
            out = one.put([], [])
        one.flush(uid)
        assert toks == got[uid]


def _logits_of(eng, uid, prompt, n_decode):
    out = eng.put([uid], [prompt])
    while uid not in out:
        out = eng.put([], [])
    rows = [out[uid]]
    for _ in range(n_decode):
        eng.extend(uid, int(rows[-1].argmax()))
        rows.append(eng.put([], [])[uid])
    return np.stack(rows)


def test_a_flushed_slot_is_reused_from_zero_state():
    model, eng = tiny_engine(state_manager={"max_tracked_sequences": 1,
                                            "max_ragged_batch_size": 16})
    rng = np.random.default_rng(2)
    first = rng.integers(0, model.vocab_size, 30).tolist()
    second = rng.integers(0, model.vocab_size, 19).tolist()
    _logits_of(eng, 1, first, 2)
    slot = eng.state_manager.get(1).slot
    eng.flush(1)
    assert float(jnp.abs(eng.state["ssm"][:, slot]).max()) > 0   # left as is
    got = _logits_of(eng, 2, second, 3)
    assert eng.state_manager.get(2).slot == slot
    _, fresh = tiny_engine()
    np.testing.assert_allclose(got, _logits_of(fresh, 2, second, 3),
                               atol=1e-5)


def test_preempt_and_recompute_gives_the_undisturbed_logits():
    model, eng = tiny_engine()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, model.vocab_size, 21).tolist()
    want = _logits_of(eng, 1, prompt, 5)
    eng.flush(1)

    rows = _logits_of(eng, 2, prompt, 2)
    eng.extend(2, int(rows[-1].argmax()))
    tokens = eng.preempt(2)             # prompt + 3 sampled, one uncached
    assert len(tokens) == len(prompt) + 3
    got = _logits_of(eng, 3, tokens, 2)
    np.testing.assert_allclose(got, want[3:], atol=1e-5)


def test_schedule_span_counts_the_mixers_work():
    from deepspeed_tpu.telemetry.tracing import Tracer

    model, eng = tiny_engine()
    eng.tracer = Tracer(enabled=True)
    alloc = [e for e in eng.tracer.snapshot() if e["name"] == "v2.state_alloc"]
    assert len(alloc) == 1 and alloc[0]["args"]["ssm_bytes"] == int(
        eng.state["ssm"].nbytes) and alloc[0]["args"]["ssm_impl"] == "ssd_xla"
    slot_bytes = int(eng.state["ssm"].nbytes) // 5
    assert slot_bytes == 2 * 4 * 32 * 16 * 4
    assert eng.state_bytes == sum(int(a.nbytes) for a in eng.state.values())
    eng.admit(1, list(range(1, 21)))
    eng.step()                           # 16 of 20 rows, from zeros
    eng.step()                           # the other 4, from the slot
    sched = [e["args"] for e in eng.tracer.snapshot()
             if e["name"] == "v2.schedule"]
    assert [(a["ssm_runs"], a["ssm_rows"], a["state_slots_live"],
             a["state_bytes"]) for a in sched] == [
                 (1, 16, 1, slot_bytes), (1, 4, 1, 2 * slot_bytes)]
    assert ssm_step_counts([(0, 5), (7, 1), (9, 1)], 100, 3) == {
        "ssm_runs": 3, "ssm_rows": 7, "state_slots_live": 3,
        "state_bytes": 500}
    assert eng.kv_geometry()[-1] == ("recurrent_state_bytes_per_seq",
                                     eng.state_bytes // 5)


# -- what cannot carry recurrent state refuses, by name ---------------------
def test_paths_that_need_a_state_snapshot_refuse():
    model, eng = tiny_engine()
    eng.admit(1, list(range(1, 30)))
    eng.step()
    eng.step()
    refused = {
        "prefix adoption": lambda: eng.admit(2, list(range(1, 30)),
                                             cached_blocks=[5], num_cached=8),
        "verify_step": lambda: eng.verify_step({1: [3, 4]}),
        "rewind": lambda: eng.rewind(1, list(range(1, 20)), 16),
        "export": lambda: eng.export_kv_chain(1),
        "import": lambda: eng.import_kv_chain({"geom": eng.kv_geometry(),
                                               "tokens": []}),
        "audit verify": lambda: eng.audit_step_args("verify"),
    }
    for what, call in refused.items():
        with pytest.raises(RecurrentStateUnsupported,
                           match="Mamba-2 SSM mixer") as e:
            call()
        assert "state snapshots" in str(e.value), what
    assert 2 not in eng.state_manager


def test_the_server_refuses_the_three_options():
    from deepspeed_tpu.serving import InferenceServer

    model, eng = tiny_engine()
    with pytest.raises(RecurrentStateUnsupported, match="prefix_cache"):
        InferenceServer(eng, {"prefix_cache": {"enabled": True}})
    with pytest.raises(RecurrentStateUnsupported, match="speculative"):
        InferenceServer(eng, {}, spec_decoder=object())
    srv = InferenceServer(eng, {})
    for kw in ({"handoff": True}, {"kv_payload": {"tokens": []}}):
        with pytest.raises(RecurrentStateUnsupported,
                           match="SSM mixer.*state snapshots.*hand-off"):
            srv.submit([1, 2, 3], **kw)


def test_the_server_serves_it():
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    model, eng = tiny_engine()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, model.vocab_size, n).tolist()
               for n in (33, 6, 18)]
    srv = InferenceServer(eng, {}).start()
    try:
        streams = [srv.submit(p, SamplingParams(max_new_tokens=5))
                   for p in prompts]
        got = [s.result(timeout=120) for s in streams]
    finally:
        srv.stop(drain=False, timeout=60)
    _, one = tiny_engine()
    assert got == one.generate(prompts, max_new_tokens=5)


def test_the_training_forward_refuses_the_mixer():
    model = get_model_config("falcon-h1-tiny")
    params = tf_model.init_params(model, jax.random.PRNGKey(0))
    assert set(params["layers"]["ssm"]) == {
        "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
        "out_proj"}
    with pytest.raises(NotImplementedError, match="Mamba-2 SSM mixer"):
        tf_model.forward(params, jnp.zeros((1, 8), jnp.int32), model)
    with pytest.raises(NotImplementedError, match="Mamba-2 SSM mixer"):
        tf_model.loss_fn(params, {"input_ids": jnp.zeros((1, 8), jnp.int32),
                                  "labels": jnp.zeros((1, 8), jnp.int32)},
                         model)


def test_a_caller_without_state_slots_is_refused():
    from deepspeed_tpu.inference.kv_generate import KVCachedGenerator

    model = get_model_config("falcon-h1-tiny")
    params = tf_model.init_params(model, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="Mamba-2 SSM mixer"):
        KVCachedGenerator(model, block_size=8).generate(
            params, np.ones((1, 4), np.int32), 2)


# -- a model without a mixer runs what it ran -------------------------------
def test_models_without_a_mixer_keep_their_programs():
    from deepspeed_tpu.telemetry.tracing import Tracer

    model = get_model_config("mistral-tiny")
    eng = InferenceEngineV2(model, dict(ENGINE), seed=0)
    assert eng.state is None and eng.ssm_impl is None
    assert eng.state_bytes == 0 and len(eng.kv_geometry()) == 6
    fn, args = eng.audit_step_args("decode")
    assert len(args) == 4 and fn is eng._step
    assert len(eng.audit_arg_categories()) == 4
    text = fn.lower(*args).as_text()
    assert "ssd_ragged" not in text and text.count("stablehlo.while") == 1
    eng.tracer = Tracer(enabled=True)
    eng.admit(1, list(range(1, 21)))
    for _ in range(2):
        out = eng.step()
    eng.extend(1, out[1])
    eng.step()
    assert eng._dispatched == {
        ("ragged_step_sampled", 16, 2, True, 0, True),
        ("ragged_step_sampled", 16, 4, True, 0, True)}
    names = {e["name"] for e in eng.tracer.snapshot()}
    assert "v2.state_alloc" not in names
    sched = [e["args"] for e in eng.tracer.snapshot()
             if e["name"] == "v2.schedule"]
    assert all(set(a) == {"trace_id", "span_id", "parent_id", "seqs",
                          "tokens", "prefill_tokens", "decode_tokens",
                          "blocked_rows", "one_row_walks", "kv_rows",
                          "qk_pairs",
                          "append_pages"}
               for a in sched)
    # sixteen rows from position 0 fill two pages of 8, four more a third,
    # the decode row at position 20 lands in that one
    assert [a["append_pages"] for a in sched] == [2, 1, 1]


# -- the published key names -------------------------------------------------
def test_hf_falcon_h1_round_trip():
    """A tiny random checkpoint in the published key names
    (``mamba.in_proj`` ... ``pre_ff_layernorm``) through ``hf_loader`` and
    the engine, against the published modelling code's own logits."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "FalconH1ForCausalLM"):
        pytest.skip("this transformers has no falcon_h1")
    from deepspeed_tpu.models.hf_loader import load_hf_model

    conf = transformers.FalconH1Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=8, mamba_expand=2, max_position_embeddings=128,
        rope_theta=1e6, tie_word_embeddings=False, embedding_multiplier=2.0,
        lm_head_multiplier=0.5, attention_in_multiplier=0.9,
        attention_out_multiplier=0.7, key_multiplier=0.6,
        ssm_in_multiplier=0.8, ssm_out_multiplier=1.3,
        ssm_multipliers=[0.7, 1.2, 0.9, 1.1, 0.8],
        mlp_multipliers=[1.4, 0.75], mamba_rms_norm=True,
        mamba_norm_before_gate=False)
    torch.manual_seed(0)
    hf = transformers.FalconH1ForCausalLM(conf).eval()
    with torch.no_grad():
        for name, p in hf.named_parameters():
            if any(k in name for k in ("A_log", "dt_bias", "norm")) \
                    or name.endswith(".D"):
                p.add_(torch.randn_like(p) * 0.1)
    assert {k.split("layers.0.")[1] for k in hf.state_dict()
            if "layers.0." in k} >= {
        "mamba.in_proj.weight", "mamba.conv1d.weight", "mamba.conv1d.bias",
        "mamba.dt_bias", "mamba.A_log", "mamba.D", "mamba.norm.weight",
        "mamba.out_proj.weight", "self_attn.q_proj.weight",
        "feed_forward.gate_proj.weight", "input_layernorm.weight",
        "pre_ff_layernorm.weight"}
    cfg, params = load_hf_model(hf, dtype=jnp.float32)
    assert cfg.arch == "falcon_h1" and cfg.ssm.ssm_multipliers == (
        0.7, 1.2, 0.9, 1.1, 0.8)
    ids = np.random.default_rng(0).integers(0, 256, (1, 37))
    with torch.no_grad():
        want = hf(torch.tensor(ids)).logits.numpy()[0]
    eng = InferenceEngineV2(cfg, dict(
        ENGINE, state_manager={"max_tracked_sequences": 2,
                               "max_ragged_batch_size": 16}),
        model_params=params)
    got = _logits_of(eng, 1, ids[0].tolist(), 0)[0]       # three chunks
    np.testing.assert_allclose(got, want[-1], atol=1e-5)
    # and the benchmark's reference reads the same published arithmetic
    ref = np.asarray(reference.logits(
        params, ids, dict(conf.to_dict()), jax.devices()[0]))[0]
    np.testing.assert_allclose(ref, want, atol=1e-5)
