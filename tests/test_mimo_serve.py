"""A mixed-attention model whose KINDS of layer differ
(``MixedAttentionConfig.attn_by_kind``: MiMo-V2-Flash, HF
``mimo_v2_flash``) through ``InferenceEngineV2`` at the tiny preset,
float32, against the plain reference
``benchmark/reference/mimo_v2_flash.py``: chunked prefill then decode
through both pools, past the window and across freed (and poisoned) window
pages; the sink in the softmax (its sign, its absence); keys wider than
values and KV heads by kind in the paged kernel and the append (interpret
mode) against the XLA path; the rotary share and both bases; the value
scale; the shares of an expert layer against the uncut layer; and the step
programs of the models the benchmark had, held to the parent commit's."""

import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import mimo_v2_flash as reference  # noqa: E402
from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2 import model as v2_model  # noqa: E402
from deepspeed_tpu.models import get_model_config  # noqa: E402
from deepspeed_tpu.models import transformer as tf_model  # noqa: E402
from deepspeed_tpu.moe.sharded_moe import moe_forward_held  # noqa: E402
from deepspeed_tpu.ops.pallas import kv_append, paged_attention  # noqa: E402

# window 24 = three pages of 8; a step of 16 rows; a context of 20 pages
ENGINE = {"dtype": "float32",
          "memory_config": {"num_blocks": 48, "window_blocks": 16,
                            "block_size": 8},
          "max_context": 160,
          "state_manager": {"max_tracked_sequences": 4,
                            "max_ragged_batch_size": 16}}
# float32 arithmetic on both sides: what is left is the order of the sums
# (2e-7 as read); a sink of the wrong sign or none, an unscaled value, a
# wrong rotary base or share each read 2e-3 to 7e-3 (attention is a small
# part of a seeded model's logits: its values are centred)
TOLERANCE = 2e-5
POISON = 1e30       # tests/test_trinity_serve.py has why not NaN


def reference_config(model) -> dict:
    """The published names ``reference/mimo_v2_flash.py`` reads, from a
    model."""
    mx = model.mixed
    kinds = mx.kinds(model.num_layers)
    return {"hidden_size": model.hidden_size,
            "num_hidden_layers": model.num_layers,
            "num_attention_heads": model.num_heads,
            "num_key_value_heads": model.kv_heads,
            "swa_num_key_value_heads": model.window_kv_heads,
            "head_dim": model.dim_per_head,
            "v_head_dim": model.value_width,
            "layernorm_epsilon": model.layernorm_eps,
            "rope_theta": model.rope_theta,
            "swa_rope_theta": mx.window_rope_theta,
            "partial_rotary_factor": model.rotary_pct,
            "sliding_window": mx.sliding_window,
            "attention_value_scale": mx.value_scale,
            "add_swa_attention_sink_bias": mx.window_sink,
            "add_full_attention_sink_bias": mx.full_sink,
            "hybrid_layer_pattern": [int(not full) for full, _ in kinds],
            "moe_layer_freq": [int(e) for _, e in kinds],
            "n_routed_experts": mx.experts_held[1],
            "experts_held_first": mx.experts_held[0],
            "num_experts_per_tok": mx.num_experts_per_tok,
            "norm_topk_prob": True, "routed_scaling_factor": None}


def seeded_bias(params, seed=5):
    """A non-zero selection bias (the seeded weights' is zeros), of the
    size of the scores at the edge of the choice: it must move choices."""
    bias = params["layers"]["moe"]["bias"]
    noise = jax.random.normal(jax.random.PRNGKey(seed), bias.shape) * 0.05
    return {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"], "bias": noise.astype(bias.dtype)}}}


def build(engine=None, edit=None, **overrides):
    """``(engine, model, params)``: the tiny preset with ``overrides``
    served on the weights of the preset AS PUBLISHED, which ``edit``
    (params -> params) may change for the program alone."""
    model = get_model_config("mimo-tiny", param_dtype=jnp.float32,
                             **overrides)
    params = seeded_bias(tf_model.init_params(
        get_model_config("mimo-tiny", param_dtype=jnp.float32,
                         dtype=jnp.float32), jax.random.PRNGKey(3)))
    served = edit(params) if edit else params
    return (InferenceEngineV2(model, dict(engine or ENGINE),
                              model_params=served), model, params)


def poison_free_window_pages(eng):
    bs = eng.cfg.block_size
    free = np.asarray(eng.state_manager.window_allocator._free)
    if not len(free):
        return
    rows = (free[:, None] * bs + np.arange(bs)[None]).reshape(-1)
    eng.state = {k: a.at[:, :, rows].set(POISON)
                 for k, a in eng.state.items()}


def run_through_window(eng, prompt, decode, poison=False):
    """Logits of the prompt's last position and of ``decode`` greedy steps
    after it, through ``put``; the tokens; the most window pages held;
    the window pages freed."""
    uid, rows, toks, held = 7, [], [], 0
    out = eng.put([uid], [prompt])
    while True:
        seq = eng.state_manager.get(uid)
        held = max(held, len(seq.window_blocks) - seq.window_freed)
        if poison:
            poison_free_window_pages(eng)
        if uid in out:
            rows.append(np.asarray(out[uid], np.float32))
            if len(rows) > decode:
                break
            toks.append(int(rows[-1].argmax()))
            eng.extend(uid, toks[-1])
        out = eng.put([], [])
    freed = seq.window_freed
    eng.flush(uid)
    return np.stack(rows), toks, held, freed


PROMPT = np.random.default_rng(1).integers(0, 512, size=70).tolist()


def error_against_reference(eng, params, decode=6, poison=False):
    """rms(logits - reference) / rms(reference) over the prompt's last
    position and ``decode`` decoded ones, the reference on ``params`` and
    the preset's published configuration."""
    got, toks, held, freed = run_through_window(eng, PROMPT, decode, poison)
    cfg = reference_config(get_model_config("mimo-tiny"))
    ref = np.asarray(reference.logits(
        params, np.asarray([PROMPT + toks]), cfg, jax.devices()[0],
        last=decode + 1))[0]
    assert np.isfinite(got).all()
    err = np.sqrt(((got - ref) ** 2).mean() / (ref ** 2).mean())
    return err, got, ref, held, freed


def test_the_preset_differs_by_kind():
    model = get_model_config("mimo-tiny")
    assert model.mixed.attn_by_kind
    assert (model.kv_heads, model.window_kv_heads) == (2, 4)
    assert (model.dim_per_head, model.value_width) == (24, 16)
    assert (model.window_layers, model.sink_layers) == (2, 2)
    eng, _, _ = build()
    attn = eng.params["layers"]
    assert attn["attn_full"]["wk"].shape == (2, 64, 2 * 24)
    assert attn["attn_window"]["wk"].shape == (2, 64, 4 * 24)
    assert attn["attn_window"]["wv"].shape == (2, 64, 4 * 16)
    assert attn["attn_window"]["sink"].shape == (2, 4)
    assert "sink" not in attn["attn_full"] and "attn" not in attn
    assert attn["attn_full"]["wo"].shape == (2, 4 * 16, 64)
    assert "shared" not in attn["moe"]
    assert eng.cache_k.shape == (2, 2, 48 * 8, 24)
    assert eng.cache_v.shape == (2, 2, 48 * 8, 16)
    assert eng.state["k"].shape == (2, 4, 16 * 8, 24)
    assert eng.state["v"].shape == (2, 4, 16 * 8, 16)


def test_the_whole_model_and_the_share_have_the_published_shapes():
    whole = get_model_config("mimo-v2-flash")
    kinds = whole.mixed.kinds(whole.num_layers)
    assert [i for i, (full, _) in enumerate(kinds) if full] \
        == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert [e for _, e in kinds] == [False] + [True] * 47
    share = get_model_config("mimo-v2-flash-ep16")
    assert [full for full, _ in share.mixed.kinds(share.num_layers)] \
        == [True] + [False] * 5 + [True]
    shapes = jax.eval_shape(
        lambda: tf_model.init_params(share, jax.random.PRNGKey(0)))
    layers = shapes["layers"]
    assert layers["attn_full"]["wq"].shape == (2, 4096, 64 * 192)
    assert layers["attn_full"]["wk"].shape == (2, 4096, 4 * 192)
    assert layers["attn_full"]["wv"].shape == (2, 4096, 4 * 128)
    assert layers["attn_window"]["wk"].shape == (5, 4096, 8 * 192)
    assert layers["attn_window"]["wv"].shape == (5, 4096, 8 * 128)
    assert layers["attn_window"]["wo"].shape == (5, 64 * 128, 4096)
    assert layers["attn_window"]["sink"].shape == (5, 64)
    assert layers["moe"]["router"].shape == (6, 4096, 256)
    assert layers["moe"]["wg"].shape == (6, 16, 4096, 2048)
    # the pools by kind, a key row in whole lane tiles
    k, v, win = v2_model.new_window_pools(
        share, 256, 128, zeros=lambda s, dtype: jax.ShapeDtypeStruct(s, dtype))
    assert (k.shape, v.shape) == ((2, 4, 256, 256), (2, 4, 256, 128))
    assert (win["k"].shape, win["v"].shape) \
        == ((5, 8, 128, 256), (5, 8, 128, 128))


@pytest.mark.parametrize("poison", [False, True])
def test_chunked_prefill_and_decode_through_both_pools(poison):
    """A 70-token prompt in chunks of 16, then 30 decoded tokens: the
    window (24) is passed inside the prompt, pages are freed behind it
    from the fourth chunk on, and every decode row reads across a page
    edge, through the sink's softmax in the window layers."""
    eng, _, params = build()
    err, got, ref, held, freed = error_against_reference(
        eng, params, decode=30, poison=poison)
    assert err < TOLERANCE, err
    assert (got.argmax(-1) == ref.argmax(-1)).all()
    assert held <= 6 and freed == 9
    mgr = eng.state_manager
    assert mgr.window_allocator.free_blocks == 15
    assert mgr.allocator.free_blocks == 47


def test_a_sink_in_the_full_layers_too():
    """``full_sink`` (HF ``add_full_attention_sink_bias``, false as
    published): both kinds' softmax start from a sink of their own."""
    model = get_model_config("mimo-tiny", param_dtype=jnp.float32,
                             full_sink=True)
    params = seeded_bias(tf_model.init_params(
        model.replace(dtype=jnp.float32), jax.random.PRNGKey(3)))
    assert params["layers"]["attn_full"]["sink"].shape == (2, 4)
    eng = InferenceEngineV2(model, dict(ENGINE), model_params=params)
    got, toks, _, _ = run_through_window(eng, PROMPT, 4)
    cfg = dict(reference_config(model))
    assert cfg["add_full_attention_sink_bias"] and model.sink_layers == 4
    ref = np.asarray(reference.logits(
        params, np.asarray([PROMPT + toks]), cfg, jax.devices()[0],
        last=5))[0]
    assert np.sqrt(((got - ref) ** 2).mean() / (ref ** 2).mean()) < TOLERANCE


def _edit_window_attn(name, fn):
    def edit(params):
        layers = params["layers"]
        kind = {**layers["attn_window"], name: fn(layers["attn_window"][name])}
        return {**params, "layers": {**layers, "attn_window": kind}}
    return edit


# what the PROGRAM is given that the reference is not: each must read far
# off the tolerance, or the comparison does not see the mechanism
SEEN = {
    "the sink's sign": dict(edit=_edit_window_attn("sink", lambda b: -b)),
    "a zeroed sink": dict(edit=_edit_window_attn("sink", jnp.zeros_like)),
    "an unscaled value": dict(value_scale=1.0),
    "one rotary base for both kinds": dict(window_rope_theta=5e6),
    "the bases swapped": dict(rope_theta=1e4, window_rope_theta=5e6),
    "rotary on every dim": dict(rotary_pct=1.0),
    "a window one page short": dict(mixed=dataclasses.replace(
        get_model_config("mimo-tiny").mixed, sliding_window=16)),
}


@pytest.mark.parametrize("what", sorted(SEEN))
def test_the_comparison_sees(what):
    eng, _, params = build(**SEEN[what])
    err = error_against_reference(eng, params)[0]
    assert err > 50 * TOLERANCE, err


def test_the_sink_against_a_dense_softmax_with_one_more_column():
    """``_paged_attention_xla`` with a sink, against scores with the
    sink's column appended, softmaxed and the column dropped."""
    t, nh, nkv, d, dv, ctx = 5, 4, 2, 24, 16, 12
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (t, nh, d))
    k = jax.random.normal(ks[1], (nkv, ctx, d))
    v = jax.random.normal(ks[2], (nkv, ctx, dv))
    sink = jax.random.normal(ks[3], (nh,)) * 2
    pos = jnp.arange(ctx - t, ctx, dtype=jnp.int32)
    cfg = get_model_config("mimo-tiny")
    got = v2_model._paged_attention_xla(
        q, k, v, jnp.broadcast_to(jnp.arange(ctx), (t, ctx)), pos,
        jnp.full((t,), ctx, jnp.int32), cfg, sink)
    kk, vv = (jnp.repeat(a, nh // nkv, 0) for a in (k, v))
    s = jnp.einsum("thd,hcd->thc", q, kk) / np.sqrt(d)
    s = jnp.where(jnp.arange(ctx)[None, None] <= pos[:, None, None], s,
                  -jnp.inf)
    s = jnp.concatenate([s, jnp.broadcast_to(sink[None, :, None],
                                             (t, nh, 1))], -1)
    want = jnp.einsum("thc,hcd->thd", jax.nn.softmax(s, -1)[..., :-1], vv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert got.shape == (t, nh, dv)
    # the sink takes probability: without it the rows weigh more
    bare = v2_model._paged_attention_xla(
        q, k, v, jnp.broadcast_to(jnp.arange(ctx), (t, ctx)), pos,
        jnp.full((t,), ctx, jnp.int32), cfg)
    assert not np.allclose(got, bare, atol=1e-3)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(paged_attention, "INTERPRET", True)


def _paged_case(nkv, window, sink, seed=0, d=32, dv=16, bs=8, nh=8):
    """Three sequences (a chunk of 11 rows deep in its context, a decode
    row, a fresh prompt of 6) over pools of ``nkv`` KV heads, K rows
    ``d`` and V rows ``dv`` wide."""
    rng = np.random.default_rng(seed)
    n_pages, layers, layer = 24, 3, 1
    k_pool = jnp.asarray(rng.normal(size=(layers, nkv, n_pages * bs, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(layers, nkv, n_pages * bs, dv)),
                         jnp.float32)
    ctx = [(40, 11), (29, 1), (6, 6)]           # (context after, new rows)
    tables = np.zeros((4, 6), np.int32)
    pages = rng.permutation(np.arange(1, n_pages))
    slot, pos, at = [], [], 0
    for s, (end, n) in enumerate(ctx):
        need = -(-end // bs)
        tables[s, :need] = pages[at:at + need]
        at += need
        slot += [s] * n
        pos += list(range(end - n, end))
    slot, pos = np.asarray(slot, np.int32), np.asarray(pos, np.int32)
    clen = np.asarray([ctx[s][0] for s in slot], np.int32)
    q = jnp.asarray(rng.normal(size=(len(slot), nh, d)), jnp.float32)
    sink_v = jnp.asarray(rng.normal(size=(nh,)), jnp.float32) if sink \
        else None
    return (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(slot),
            jnp.asarray(pos), jnp.asarray(clen), layer, bs, window, sink_v)


@pytest.mark.parametrize("nkv,window,sink", [
    (4, None, False), (8, 24, True), (8, 8, True), (2, None, True)])
def test_paged_qblock_takes_two_widths_heads_by_kind_and_a_sink(
        interpreted, nkv, window, sink):
    (q, k_pool, v_pool, tables, slot, pos, clen, layer, bs, window,
     sink_v) = _paged_case(nkv, window, sink)
    got = paged_attention.paged_decode_attention(
        q, k_pool, v_pool, tables, pos, clen, bs, 1 / np.sqrt(24),
        window=window, token_slot=slot, layer=layer, sink=sink_v)
    c = jnp.arange(tables.shape[1] * bs)
    idx = (tables[:, c // bs] * bs + c % bs)[slot]
    cfg = get_model_config("mimo-tiny").replace(
        sliding_window=window, attn_scale=1 / np.sqrt(24))
    want = v2_model._paged_attention_xla(
        q, k_pool[layer], v_pool[layer], idx, pos, clen, cfg, sink_v)
    assert got.shape == (q.shape[0], q.shape[1], 16)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_int8_kernel_refuses_a_sink_and_unequal_widths_by_name():
    q = jnp.zeros((2, 4, 32))
    k8 = jnp.zeros((1, 2, 16, 32), jnp.int8)
    sc = jnp.ones((1, 2, 16))
    args = (jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.int32), 8, 1.0)
    with pytest.raises(NotImplementedError, match="paged_decode_q8"):
        paged_attention.paged_decode_attention(
            q, k8, k8, *args, k_scales=sc, v_scales=sc, layer=0,
            sink=jnp.zeros((4,)))
    with pytest.raises(NotImplementedError, match="ONE width"):
        paged_attention.paged_decode_attention(
            q, k8, k8[..., :16], *args, k_scales=sc, v_scales=sc, layer=0)


def test_what_the_kernel_takes():
    assert paged_attention.supports(128, 192, 128)      # kept in 256 lanes
    assert paged_attention.row_width(192) == 256
    assert paged_attention.row_width(128) == 128
    assert paged_attention.row_width(24) == 24
    assert paged_attention.supports(16, 128)
    assert not paged_attention.supports(16, 64)         # half a lane tile
    assert not paged_attention.supports(16, 192, 96)    # the output's width
    assert not paged_attention.supports(4, 128)


@pytest.mark.parametrize("nkv,d,dv", [(4, 256, 128), (8, 256, 128),
                                      (2, 128, 128)])
def test_kv_append_takes_rows_of_two_widths(interpreted, nkv, d, dv):
    """The page-granular append against the row scatter, bit for bit, K
    rows and V rows each of a width of their own."""
    bs, n_pages, layers, layer, t = 8, 12, 2, 1, 24
    rng = np.random.default_rng(nkv)
    pool = lambda w: jnp.asarray(
        rng.normal(size=(layers, nkv, n_pages * bs, w)), jnp.bfloat16)
    ck, cv = pool(d), pool(dv)
    k = jnp.asarray(rng.normal(size=(t, nkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(t, nkv, dv)), jnp.bfloat16)
    # 13 rows from row 5 of page 3 on (pages 3, 7, 2), a decode row, and
    # padding rows aimed at page 0
    dest = np.zeros((t,), np.int32)
    run = [3 * bs + 5 + i for i in range(3)] + [7 * bs + i for i in range(8)] \
        + [2 * bs + i for i in range(2)]
    dest[:13] = run
    dest[13] = 9 * bs + 6
    dest = jnp.asarray(dest)
    pages = kv_append.step_pages(ck, dest, bs, cv)
    assert pages is not None
    got_k, got_v = kv_append.kv_append(ck, cv, k, v, pages, layer, bs)
    want_k = v2_model._kv_append(ck, k, dest, layer)
    want_v = v2_model._kv_append(cv, v, dest, layer)
    live = slice(bs, None)          # page 0 is the garbage page
    assert (got_k[:, :, live] == want_k[:, :, live]).all()
    assert (got_v[:, :, live] == want_v[:, :, live]).all()
    assert got_v.shape == cv.shape


def test_a_key_row_past_a_lane_tile_is_kept_in_whole_tiles(interpreted):
    """The engine's own path with a K pool wider than the head (the
    published 192 in 256): queries and keys are padded with zeros, and
    the kernels read and append the pool's width.  Tiny: head 136 in 256
    lanes, values 128, pages of 8, ``paged_pallas`` pinned and
    interpreted, against the same model on the XLA path."""
    kw = dict(hidden_size=32, head_dim=136, v_head_dim=128, num_heads=2,
              num_kv_heads=1, window_kv_heads=2, num_layers=2,
              layer_types=("full_attention", "sliding_attention"),
              n_routed_experts=4, experts_held=(0, 4), intermediate_size=32,
              moe_intermediate_size=16, vocab_size=64, dtype=jnp.float32,
              param_dtype=jnp.float32)
    engine = dict(ENGINE, state_manager={"max_tracked_sequences": 2,
                                         "max_ragged_batch_size": 16})
    prompt = list(range(1, 45))
    outs = {}
    for impl in ("paged_pallas", "paged_xla"):
        model = get_model_config("mimo-tiny",
                                 v2_modules=(("attention", impl),), **kw)
        eng = InferenceEngineV2(model, dict(engine), seed=1)
        assert eng.attention_impl == impl
        assert eng.cache_k.shape[-1] == 256 and eng.cache_v.shape[-1] == 128
        assert eng.state["k"].shape[1:] == (2, 16 * 8, 256)
        outs[impl] = run_through_window(eng, prompt, 3)[0]
        if impl == "paged_pallas":
            calls = eng._state_alloc["kernel_calls_per_step"]
            fn, args = eng.audit_step_args("decode")
            text = str(jax.make_jaxpr(fn)(*args))
            assert calls == 4 == text.count("name=paged_qblock") \
                + text.count("name=kv_append")
            assert eng._state_alloc["full_page_bytes"] \
                == 1 * 8 * (256 + 128) * 4
            assert eng._state_alloc["window_page_bytes"] \
                == 2 * 8 * (256 + 128) * 4
    np.testing.assert_allclose(outs["paged_pallas"], outs["paged_xla"],
                               rtol=2e-4, atol=2e-4)


def test_shares_add_up_to_the_uncut_layer():
    """The four shares of the tiny preset's 16 experts, each through the
    PROGRAM's held layer with the router over all 16, add up to the uncut
    reference's layer (no shared expert to count once)."""
    model = get_model_config("mimo-tiny", experts_held=(0, 16),
                             param_dtype=jnp.float32, dtype=jnp.float32)
    moe = seeded_bias(tf_model.init_params(
        model, jax.random.PRNGKey(4)))["layers"]["moe"]
    mx = model.mixed
    m = jax.random.normal(jax.random.PRNGKey(9), (48, model.hidden_size))
    m = m + 0.5                     # the stream's shared part, as seeded
    layer = 1
    total, chosen_somewhere = 0.0, 0
    for first in range(0, 16, 4):
        share = {**moe, **{n: moe[n][:, first:first + 4]
                           for n in ("wg", "wi", "wo")}}
        part = moe_forward_held(m, share, layer, first=first,
                                top_k=mx.num_experts_per_tok,
                                scale=mx.route_scale)
        chosen_somewhere += bool(jnp.abs(part).sum() > 0)
        total = total + part
    assert chosen_somewhere >= 2
    cfg = dict(reference_config(model), n_routed_experts=16,
               experts_held_first=0)
    with jax.default_matmul_precision("highest"):
        ref = reference.expert_layer(cfg, jax.devices()[0])(m[None], moe,
                                                            layer)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_the_schedule_span_counts_pairs_by_kind():
    from deepspeed_tpu.inference.v2.engine_v2 import window_step_counts

    model = get_model_config("mimo-tiny")       # window 24
    # a chunk of 16 rows onto 40 cached, a decode row at 29, a prompt of 6
    counts = window_step_counts([(40, 16), (29, 1), (0, 6)], model,
                                (11, 7), 2)
    assert counts["full_qk_pairs"] == sum(range(41, 57)) + 30 + 21
    assert counts["window_qk_pairs"] == 16 * 24 + 24 + 21
    assert counts["full_kv_rows"] == 56 + 30 + 6
    assert counts["window_kv_rows"] == 24 + 24 + 6
    assert counts["expert_rows"] == 23 * 2 * 4 / 16
    assert (counts["full_pages"], counts["window_pages"],
            counts["pages_freed"]) == (11, 7, 2)


def test_generate_and_server_streams_agree():
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, size=n).tolist()
               for n in (50, 9, 33, 70, 21)]
    eng, _, _ = build()
    want = eng.generate(prompts, max_new_tokens=20)
    eng, _, _ = build()
    srv = InferenceServer(eng, {})
    srv.start()
    try:
        streams = [srv.submit(p, SamplingParams(max_new_tokens=20))
                   for p in prompts]
        got = [list(s) for s in streams]
    finally:
        srv.stop(drain=False, timeout=30)
    assert got == want
    assert eng.free_window_blocks == 15 and eng.free_blocks == 47


# sha256 (16 hex digits) of the lowered decode-bucket step of each tiny
# model of the benchmark's configurations, read on the parent commit
# (ecb857c) with these engine configurations under this suite's conftest:
# pools, kernels' callers and the mixed trunk changed under them, and each
# gets the program it got before
PARENT_STEP = {"mistral-tiny": "c9237077b6f347ef",
               "falcon-h1-tiny": "0a917515148bfc20",
               "trinity-tiny": "018de9c12aa479b4",
               "nemotron-h-tiny": "63f9f66abbc2e031",
               "dots3-note-tiny": "771bfd007280ee70",
               "glm-5-tiny": "ae31c93635ea6b34"}
PARENT_ENGINE = {"dtype": "float32",
                 "memory_config": {"num_blocks": 32, "window_blocks": 16,
                                   "block_size": 8},
                 "max_context": 64,
                 "state_manager": {"max_tracked_sequences": 4,
                                   "max_ragged_batch_size": 32}}


@pytest.mark.parametrize("name", sorted(PARENT_STEP))
def test_other_models_step_programs_are_the_parents(name):
    eng = InferenceEngineV2(get_model_config(name), dict(PARENT_ENGINE))
    fn, args = eng.audit_step_args("decode")
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_STEP[name]
