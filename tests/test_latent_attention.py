"""Latent (MLA) attention with a learned indexer on the serving path
(``dots3-note-tiny``): chunked prefill and decode through the cache
against the benchmark's plain reference, the indexer's choice against the
reference's, the absorbed form against the expanded one, the window
layers' rings once they have wrapped, two sequences in one step, the
routed experts' shares, the step's counters, and every path that would
need a copy of a window layer's rows refusing by name."""

import dataclasses
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.inference.v2 import InferenceEngineV2, latent
from deepspeed_tpu.inference.v2 import model as v2_model
from deepspeed_tpu.inference.v2.engine_v2 import RecurrentStateUnsupported
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.models import transformer as tf_model
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import moe_forward_held, route_sigmoid

reference = importlib.import_module("benchmark.reference.dots3_note")

PRESET = "dots3-note-tiny"


def reference_config(model):
    """The published names ``benchmark/reference/dots3_note.py`` reads,
    from the program's own configuration."""
    m = model.mla
    cfg = {"hidden_size": model.hidden_size,
           "rms_norm_eps": model.layernorm_eps,
           "apply_mla_qkv_lora_rescale": m.lora_rescale,
           "num_hidden_layers": model.num_layers,
           "layer_types": list(m.layer_types),
           "first_k_dense_replace": m.first_k_dense,
           "sliding_window_size": m.sliding_window,
           "index_topk": m.index_topk, "index_n_heads": m.index_heads,
           "index_head_dim": m.index_head_dim,
           "num_experts_per_tok": m.num_experts_per_tok,
           "n_routed_experts": m.experts_held[1],
           "experts_held_first": m.experts_held[0],
           "routed_scaling_factor": 1.0, "norm_topk_prob": True}
    for prefix, w in (("", m.full), ("swa_", m.window)):
        cfg.update({
            ("num_attention_heads" if not prefix
             else "swa_num_attention_heads"): w.num_heads,
            prefix + "q_lora_rank": w.q_lora_rank,
            prefix + "kv_lora_rank": w.kv_lora_rank,
            prefix + "qk_nope_head_dim": w.qk_nope_head_dim,
            prefix + "qk_rope_head_dim": w.qk_rope_head_dim,
            prefix + "v_head_dim": w.v_head_dim,
            ("rope_theta" if not prefix else "swa_rope_theta"): w.rope_theta})
    return cfg


def engine(model=None, budget=16, block_size=4, blocks=160, context=256,
           seqs=4, seed=3, **kw):
    model = model or get_model_config(PRESET)
    return InferenceEngineV2(model, {
        "dtype": "float32",
        "memory_config": {"num_blocks": blocks, "block_size": block_size},
        "max_context": context,
        "state_manager": {"max_tracked_sequences": seqs,
                          "max_ragged_batch_size": budget}, **kw}, seed=seed)


def nonzero_bias(eng, seed=11):
    """A selection bias large enough to change choices (the benchmark's
    weights have zeros): choice by ``s + b``, weight by ``s``."""
    moe = eng.params["layers"]["moe"]
    moe["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed),
                                          moe["bias"].shape)


def serve(eng, prompts, decode):
    """Admit ``prompts`` {uid: tokens} together, prefill in chunks, then
    ``decode`` greedy tokens each: {uid: (logit rows, tokens)}."""
    out = eng.put(list(prompts), [list(p) for p in prompts.values()])
    rows = {u: [] for u in prompts}
    toks = {u: [] for u in prompts}
    while len(out) < len(prompts) or any(not rows[u] for u in prompts):
        for u, row in out.items():
            rows[u].append(np.asarray(row))
        if all(rows[u] for u in prompts):
            break
        out = eng.put([], [])
    for _ in range(decode):
        for u in prompts:
            toks[u].append(int(rows[u][-1].argmax()))
            eng.extend(u, toks[u][-1])
        for u, row in eng.put([], []).items():
            rows[u].append(np.asarray(row))
    return {u: (np.stack(rows[u]), toks[u]) for u in prompts}


def reference_logits(eng, tokens, last, selected=None):
    return np.asarray(reference.logits(
        eng.params, np.asarray([tokens]), reference_config(eng.model_config),
        jax.devices()[0], last=last, selected=selected))[0]


# contexts run well past index_topk (8) and the window (5); "wrapped": the
# ring of 128 rows has been written round more than once; "blocks": a
# step's rows are cut into blocks of 8, some of one run and some mixed
# "walked": the full layers' read pinned to the kernel, interpreted (off the
# TPU a step program gathers)
@pytest.mark.parametrize("case,prompt,budget,block_size,window_block", [
    ("short chunks", 40, 16, 4, 128),
    ("wrapped ring", 170, 32, 8, 128),
    ("blocks of one run and mixed", 75, 32, 4, 8),
    ("short chunks, walked", 40, 16, 4, 128),
    ("rows past a query block, walked", 75, 64, 8, 128)])
def test_prefill_in_chunks_then_decode_is_the_reference(
        case, prompt, budget, block_size, window_block, monkeypatch):
    monkeypatch.setattr(latent, "WINDOW_BLOCK", window_block)
    pinned = {}
    if case.endswith("walked"):
        from deepspeed_tpu.ops.pallas import latent_read

        monkeypatch.setattr(latent_read, "INTERPRET", True)
        pinned = {"modules": {"latent_read": "latent_read_walk"}}
    eng = engine(budget=budget, block_size=block_size, **pinned)
    nonzero_bias(eng)
    if case == "wrapped ring":
        assert eng.state["win"].shape[2] == 128 < prompt
    ids = np.random.default_rng(0).integers(0, 512, size=prompt).tolist()
    got, toks = serve(eng, {7: ids}, decode=6)[7]
    want = reference_logits(eng, ids + toks, last=7)
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_rows_of_two_sequences_in_one_step():
    """Two prompts prefill side by side (their chunks share steps) and
    decode side by side: each reads its own pages and its own ring."""
    eng = engine(budget=32)
    rng = np.random.default_rng(1)
    a = rng.integers(0, 512, size=45).tolist()
    b = rng.integers(0, 512, size=23).tolist()
    out = serve(eng, {1: a, 2: b}, decode=5)
    for uid, ids in ((1, a), (2, b)):
        got, toks = out[uid]
        want = reference_logits(eng, ids + toks, last=6)
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_the_indexer_chooses_what_the_reference_chooses():
    """The first layer's chosen sets for the last rows of a prompt, from
    the program's cache, beside the reference's for the same rows: the
    same sets.  (Equal scores are chosen by position in both, the lower
    first: ``test_the_selection_is_a_stable_sorts_choice`` holds the
    program to that; with seeded normal weights no two scores are
    equal.)"""
    eng = engine(budget=16)
    ids = np.random.default_rng(2).integers(0, 512, size=60).tolist()
    eng.put([5], [ids])
    while eng.state_manager.get(5).uncached:
        eng.put([], [])
    seq = eng.state_manager.get(5)
    rows = np.arange(20, 60, dtype=np.int32)
    tables = np.zeros((1, 16), np.int32)
    tables[0, :len(seq.blocks)] = seq.blocks
    # the first layer's input is the embedding alone: its choice can be
    # asked from outside a step
    model, layers = eng.model_config, eng.params["layers"]
    p = jax.tree.map(lambda a: a[0], layers["full"])
    h = latent._rms(eng.params["embed"]["tokens"][np.asarray(ids)[rows]],
                    layers["ln1"]["scale"][0], model)
    c_q, _, _ = latent._project(h, p, model.mla.full, rows, model)
    q_i, _, w_i = latent._index_inputs(h, c_q, p, rows, model)
    scores = latent.index_scores_xla(
        q_i, w_i, eng.cache_v, 0, tables, np.zeros(len(rows), np.int32),
        rows, np.full(len(rows), 60, np.int32), block_size=4)
    sel, ok = latent.select_keys(scores, model.mla.index_topk)
    sets = []
    reference_logits(eng, ids, last=1, selected=sets)
    want = np.asarray(sets[0])
    assert len(sets) == 2 and want.shape == (60, 60)
    shared = total = 0
    for i, t in enumerate(rows):
        chosen = set(np.asarray(sel[i])[np.asarray(ok[i])].tolist())
        theirs = set(np.flatnonzero(want[t]).tolist())
        assert len(theirs) == 8 and max(theirs) <= t
        shared += len(chosen & theirs)
        total += len(theirs)
    assert shared == total
    # while the context is shorter than index_topk every key is chosen
    assert (want[:8].sum(1) == np.arange(1, 9)).all()


@pytest.mark.parametrize("rows,ctx,k", [(16, 64, 8), (48, 1024, 100),
                                        (128, 512, 64), (64, 256, 256)])
def test_the_selection_is_a_stable_sorts_choice(rows, ctx, k):
    """``select_keys`` (bisection on the scores' bits, compaction by
    counting: no sort) against a stable descending sort: rows that see
    one key, exactly k, k + 1 or all; a row of whole numbers (many equal
    scores, -0.0 among them) and a row of one value, where the lower
    positions win."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, ctx)).astype(np.float32)
    n = rng.integers(1, ctx + 1, size=rows)
    n[:4] = ctx, 1, k, min(ctx, k + 1)
    x = np.where(np.arange(ctx)[None] < n[:, None], x, -np.inf)
    x[4] = np.where(np.isfinite(x[4]), np.round(x[4]), x[4])
    x[5] = np.where(np.isfinite(x[5]), 0.0, x[5])
    pos, ok = jax.jit(latent.select_keys, static_argnums=1)(jnp.asarray(x), k)
    pos, ok = np.asarray(pos), np.asarray(ok)
    assert pos.shape == ok.shape == (rows, min(k, ctx))
    order = np.argsort(-x, axis=1, kind="stable")[:, :k]
    for t in range(rows):
        want = {c for c in order[t].tolist() if np.isfinite(x[t, c])}
        assert set(pos[t][ok[t]].tolist()) == want, t
        assert ok[t].sum() == len(want) == min(k, n[t])


@pytest.mark.parametrize("kind", ["full", "window"])
def test_absorbed_scores_and_values_are_the_expanded_ones(kind):
    model = get_model_config(PRESET, dtype=jnp.float32)
    w = getattr(model.mla, kind)
    params = tf_model.init_params(model, jax.random.PRNGKey(4))
    p = jax.tree.map(lambda a: a[0], params["layers"][kind])
    t = 9
    h = jax.random.normal(jax.random.PRNGKey(5), (t, model.hidden_size))
    pos = jnp.arange(t, dtype=jnp.int32)
    c_q, q, row = latent._project(h, p, w, pos, model)
    assert q.shape == (t, w.num_heads, 128) and row.shape == (t, 128)
    assert not np.asarray(row[:, w.row_dim:]).any()
    # expanded: a key and a value of their own for every head
    c_kv, k_r = row[:, :w.kv_lora_rank], row[:, w.kv_lora_rank:w.row_dim]
    k_nope = jnp.einsum("sr,hnr->shn", c_kv, p["wk_b"])
    value = jnp.einsum("sr,hrv->shv", c_kv, p["wv_b"])
    q_full = (c_q @ p["wq_b"]).reshape(t, w.num_heads, w.qk_head_dim)
    q_rope = latent._rope(q_full[..., w.qk_nope_head_dim:], pos, w.rope_theta)
    expanded = jnp.einsum("thn,shn->ths", q_full[..., :w.qk_nope_head_dim],
                          k_nope) + jnp.einsum("thr,sr->ths", q_rope, k_r)
    absorbed = jnp.einsum("thd,sd->ths", q, row)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5)
    probs = jax.nn.softmax(expanded / np.sqrt(w.qk_head_dim), -1)
    out = jnp.einsum("ths,shv->thv", probs, value)
    ctx = jnp.einsum("ths,sr->thr", probs, c_kv)
    np.testing.assert_allclose(jnp.einsum("thr,hrv->thv", ctx, p["wv_b"]),
                               out, atol=1e-5)


# (preset, where the layer's weights are, its widths)
_HEAD_PRODUCTS = {
    "dots3_full": ("dots3-note-tiny", ("layers", "full"), "full"),
    "dots3_window": ("dots3-note-tiny", ("layers", "window"), "window"),
    "glm5_full": ("glm-5-tiny", ("layers", "full"), "full"),
    "glm5_module": ("glm-5-tiny", ("mtp", "full"), "full"),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(_HEAD_PRODUCTS))
def test_head_products_behind_the_barrier_are_the_plain_products(
        case, dtype, monkeypatch):
    """``_project``'s ``(c_q, q, row)`` and ``_index_inputs``' ``q_i``, as
    a jitted program makes them with ``wq_b``'s and ``idx_wq``'s products
    behind their barrier, are the plain products' to the bit: ``c_q @ w``
    rounded to the rows' dtype AT the product (where the TPU's compiler
    rounded the folded product: PERF.md, PR 49), cut into heads, then
    rotary and the absorption.  A product kept in float32 past the
    reshape, or rounded before it is summed, moves bfloat16's ``q`` and
    ``q_i`` in the last place of most elements.  Full and window layers
    of both tiny presets and the module.  ``c_q`` and ``row``, which the
    barrier's lines do not make, are those of the program without it."""
    preset, where, kind = _HEAD_PRODUCTS[case]
    model = get_model_config(preset, dtype=getattr(jnp, dtype),
                             param_dtype=getattr(jnp, dtype))
    m, w, dt = model.mla, getattr(model.mla, kind), model.dtype
    params = tf_model.init_params(model, jax.random.PRNGKey(4))
    p = params[where[0]][where[1]]
    if where[0] == "layers":
        # the last of the stack: a layer a scan would slice out
        p = jax.tree.map(lambda a: a[-1], p)
    t = 9
    h = jax.random.normal(jax.random.PRNGKey(5), (t, model.hidden_size), dt)
    pos = jnp.arange(3, 3 + t, dtype=jnp.int32)
    c_q, q, row = jax.jit(
        lambda h, p: latent._project(h, p, w, pos, model))(h, p)
    assert c_q.dtype == q.dtype == row.dtype == dt
    with monkeypatch.context() as patch:
        patch.setattr(latent, "_head_product",
                      lambda x, w: jnp.matmul(x, w.astype(x.dtype)))
        folded = jax.jit(
            lambda h, p: latent._project(h, p, w, pos, model))(h, p)
    bits = jnp.finfo(dt).nmant

    def heads(c_q, weight, n, d, keep=False):
        """The plain product in heads: the float32 sum of ``c_q @ w``
        rounded to the rows' dtype AT the product, spelled so that no
        compiler may move the rounding (``keep``: not rounded there)."""
        y = jnp.matmul(c_q, weight.astype(dt),
                       preferred_element_type=jnp.float32)
        if not keep:
            y = lax.reduce_precision(y, 8, bits).astype(dt)
        return y.reshape(t, n, d)

    def plain_q(c_q, p, keep=False):
        y = heads(c_q, p["wq_b"], w.num_heads, w.qk_head_dim, keep)
        nope = y[..., :w.qk_nope_head_dim].astype(dt)
        return jnp.concatenate([
            jnp.einsum("thn,hnr->thr", nope, p["wk_b"].astype(dt)),
            latent._rope(y[..., w.qk_nope_head_dim:], pos, w.rope_theta,
                         m.rope_interleaved).astype(dt)], -1)

    def same(got, want):
        assert got.dtype == want.dtype == dt
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))

    same(c_q, folded[0])
    same(row, folded[2])
    same(q[..., :w.row_dim], jax.jit(plain_q)(c_q, p))
    assert not np.asarray(q[..., w.row_dim:], np.float32).any()
    if dtype == "bfloat16":
        # kept in float32 through rotary and rounded once after it, the
        # product gives another q: what this test is there to see
        late = jax.jit(lambda c_q, p: plain_q(c_q, p, keep=True))(c_q, p)
        assert (np.asarray(late, np.float32)
                != np.asarray(q[..., :w.row_dim], np.float32)).any()
    if kind == "window":
        assert "idx_wq" not in p
        return
    q_i, _, _ = jax.jit(
        lambda h, c, p: latent._index_inputs(h, c, p, pos, model))(h, c_q, p)

    @jax.jit
    def plain_qi(c_q, p):
        y, rot = heads(c_q, p["idx_wq"], m.index_heads,
                       m.index_head_dim), m.index_rope_dim
        return jnp.concatenate([
            latent._rope(y[..., :rot], pos, m.full.rope_theta,
                         m.rope_interleaved), y[..., rot:]], -1)

    same(q_i, plain_qi(c_q, p))


# -- the rings ---------------------------------------------------------------
def test_a_window_layer_keeps_the_window_and_one_step():
    """The ring is the window plus one step's rows in whole lane tiles,
    whatever the context, and after a long sequence it holds the last
    ``ring`` positions' rows and nothing older."""
    model = get_model_config(PRESET)
    eng = engine(model, budget=16)
    ring = eng.state["win"].shape[2]
    assert ring == latent.ring_rows(model, 16) == 128
    assert ring < model.mla.sliding_window + 16 + 128
    assert eng.state["win"].shape == (3, 5, 128, 128)
    assert eng.cache_k.shape == (2, 160 * 4, 128)       # rows of 32 stored
    assert eng.cache_v.shape == (2, 160 * 4, 16)
    ids = np.random.default_rng(3).integers(0, 512, size=200).tolist()
    eng.put([9], [ids])
    while eng.state_manager.get(9).uncached:
        eng.put([], [])
    slot = eng.state_manager.get(9).slot
    held = np.asarray(eng.state["win"][:, slot])
    # a second engine that saw only the last 128 + 5 tokens' worth of
    # rows cannot be compared row for row (a row depends on the whole
    # prefix); what can be read: every ring place was written (no zeros
    # row left), and the place of position p is p % ring
    assert (np.abs(held[..., :48]).sum(-1) > 0).all()
    before = held.copy()
    eng.extend(9, 1)
    eng.put([], [])
    after = np.asarray(eng.state["win"][:, slot])
    changed = np.flatnonzero((after != before).any(axis=(0, 2)))
    assert changed.tolist() == [200 % ring]


def test_a_flushed_slot_is_reused_without_clearing():
    eng = engine(budget=16, seqs=1)
    rng = np.random.default_rng(4)
    first = rng.integers(0, 512, size=50).tolist()
    serve(eng, {1: first}, decode=2)
    eng.flush(1)
    second = rng.integers(0, 512, size=30).tolist()
    got, toks = serve(eng, {2: second}, decode=3)[2]
    want = reference_logits(eng, second + toks, last=4)
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_generate_runs_the_fused_decode_loop():
    eng = engine(budget=16)
    prompts = [list(range(3, 30)), list(range(40, 52))]
    out = eng.generate(prompts, max_new_tokens=6)
    for prompt, new in zip(prompts, out):
        want = reference_logits(eng, prompt + new, last=7)
        assert new == want[:-1].argmax(-1).tolist()


# -- the experts -------------------------------------------------------------
def _layer(seed=0, experts=16, hidden=32, width=24):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": jax.random.normal(k[0], (hidden, experts)),
            "bias": 0.5 * jax.random.normal(k[1], (experts,)),
            "wg": jax.random.normal(k[2], (experts, hidden, width)) * 0.2,
            "wi": jax.random.normal(k[3], (experts, hidden, width)) * 0.2,
            "wo": jax.random.normal(k[4], (experts, width, hidden)) * 0.2}


def _share(p, first, held):
    return dict(p, **{n: p[n][first:first + held] for n in ("wg", "wi", "wo")})


def _held(x, p, **kw):
    """``moe_forward_held`` of ONE layer: the second of a stack of two."""
    stack = jax.tree.map(lambda a: jnp.stack([jnp.zeros_like(a), a]), p)
    return moe_forward_held(x, stack, jnp.int32(1), **kw)


def _dense(x, p, top_k):
    """Every expert over every row, weighted by the routing."""
    s = jax.nn.sigmoid(x @ p["router"])
    chosen = np.argsort(-np.asarray(s + p["bias"]), axis=-1)[:, :top_k]
    y = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        w = np.asarray(s[t, chosen[t]], np.float64)
        w = w / w.sum()
        for e, we in zip(chosen[t], w):
            act = jax.nn.silu(x[t] @ p["wg"][e]) * (x[t] @ p["wi"][e])
            y[t] += we * np.asarray(act @ p["wo"][e], np.float64)
    return y, chosen


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 2e, 2e+1 on rank e of eight: the routed parts of the eight
    shares add up to the layer with every expert held, which is the
    dense formula; the shared expert is the caller's, counted once."""
    p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(9), (21, 32))
    whole = _held(x, p, top_k=4, first=0)
    parts = [_held(x, _share(p, 2 * e, 2), top_k=4, first=2 * e)
             for e in range(8)]
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5)
    want, chosen = _dense(x, p, 4)
    np.testing.assert_allclose(whole, want, atol=1e-5)
    # the bias moved choices, and never a weight
    s = jax.nn.sigmoid(x @ p["router"])
    plain = np.argsort(-np.asarray(s), axis=-1)[:, :4]
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()
    got_e, got_w = route_sigmoid(x, p["router"], p["bias"], 4)
    assert (np.sort(got_e, -1) == np.sort(chosen, -1)).all()
    np.testing.assert_allclose(np.asarray(got_w).sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("rows", [1, 7, 64, 300])
def test_no_token_is_dropped_at_any_batch(rows, monkeypatch):
    """Every row chooses the SAME held expert first (a bias no score
    reaches): a capacity would drop most of them; here each gets its
    expert's output."""
    p = _layer(seed=1)
    p["bias"] = p["bias"].at[5].set(50.0)
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 32))
    monkeypatch.setattr(sharded_moe, "HELD_TILE", 16)
    got = _held(x, _share(p, 4, 4), top_k=2, first=4)
    chosen, w = route_sigmoid(x, p["router"], p["bias"], 2)
    assert (np.asarray(chosen) == 5).any(-1).all()
    want = np.zeros(x.shape)
    for t in range(rows):
        for e, we in zip(np.asarray(chosen[t]), np.asarray(w[t])):
            if 4 <= e < 8:
                act = jax.nn.silu(x[t] @ p["wg"][e]) * (x[t] @ p["wi"][e])
                want[t] += we * np.asarray(act @ p["wo"][e])
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (np.abs(np.asarray(got)).sum(-1) > 0).all()


# -- counters and spans ------------------------------------------------------
def test_step_counts_against_a_hand_count():
    model = get_model_config(PRESET)       # index_topk 8, window 5
    # a decode row at context 20, a chunk of 4 rows from position 6
    got = latent.latent_step_counts([(20, 1), (6, 4)], model)
    assert got["latent_rows"] == 5
    assert got["index_pairs"] == 21 + (7 + 8 + 9 + 10)
    assert got["selected_keys"] == 8 + (7 + 8 + 8 + 8)
    # no program named, and off the TPU a program gathers
    assert got["walked_pairs"] == 0
    assert latent.latent_step_counts(
        [(20, 1), (6, 4)], model, 16, 32)["walked_pairs"] == 0
    # a program that walks multiplies every visible key of every row
    walking = model.replace(
        v2_modules=(("latent_read", "latent_read_walk"),))
    got = latent.latent_step_counts([(20, 1), (6, 4)], walking, 16, 32)
    assert got["walked_pairs"] == got["index_pairs"] == 55
    assert got["selected_keys"] == 8 + (7 + 8 + 8 + 8)
    assert got["window_keys"] == 5 + 4 * 5
    assert got["expert_rows"] == 5 * 4 * 4 / 16
    # a prompt from position 0: the first rows see fewer than the limits
    got = latent.latent_step_counts([(0, 10)], model)
    assert got["index_pairs"] == 55
    assert got["selected_keys"] == 36 + 2 * 8
    assert got["window_keys"] == 15 + 5 * 5


def test_schedule_span_carries_the_counts_for_a_latent_model_only(
        monkeypatch):
    from deepspeed_tpu.telemetry.tracing import Tracer

    names = {"latent_rows", "index_pairs", "selected_keys", "window_keys",
             "expert_rows", "walked_pairs"}
    eng = engine(budget=16)
    eng.tracer = Tracer(enabled=True)
    eng.admit(1, list(range(1, 31)))
    eng.step()
    eng.step()
    spans = eng.tracer.snapshot()
    sched = [e["args"] for e in spans if e["name"] == "v2.schedule"]
    assert len(sched) == 2 and all(names <= set(a) for a in sched)
    assert sched[0]["latent_rows"] == 16 and sched[0]["index_pairs"] == 136
    assert sched[1]["selected_keys"] == 14 * 8
    # these programs gather (no TPU here): nothing is walked
    assert [a["walked_pairs"] for a in sched] == [0, 0]
    # an engine whose programs walk says so, by the rule it traces them by
    from deepspeed_tpu.ops.pallas import latent_read

    monkeypatch.setattr(latent_read, "INTERPRET", True)
    walking = engine(budget=16, modules={"latent_read": "latent_read_walk"})
    walking.tracer = Tracer(enabled=True)
    walking.admit(1, list(range(1, 31)))
    walking.step()
    walking.step()
    sched = [e["args"] for e in walking.tracer.snapshot()
             if e["name"] == "v2.schedule"]
    assert [a["walked_pairs"] for a in sched] == [136, sum(range(17, 31))]
    assert [a["walked_pairs"] for a in sched] == [a["index_pairs"]
                                                  for a in sched]
    alloc = [e["args"] for e in spans if e["name"] == "v2.state_alloc"]
    assert alloc and alloc[0]["ring_rows"] == 128
    assert alloc[0]["window_bytes"] == eng.state_bytes
    other = InferenceEngineV2(get_model_config("mistral-tiny"), {
        "dtype": "float32", "memory_config": {"num_blocks": 32,
                                              "block_size": 4},
        "max_context": 64}, seed=0)
    other.tracer = Tracer(enabled=True)
    other.admit(1, list(range(1, 9)))
    other.step()
    for e in other.tracer.snapshot():
        assert not names & set(e.get("args", {}))


# -- refusals, by name -------------------------------------------------------
def test_paths_that_need_a_copy_of_the_window_rows_refuse():
    eng = engine(budget=16)
    eng.admit(1, list(range(1, 20)))
    eng.step()
    eng.step()
    refused = {
        "prefix adoption": lambda: eng.admit(2, list(range(1, 30)),
                                             cached_blocks=[5], num_cached=4),
        "verify_step": lambda: eng.verify_step({1: [3, 4]}),
        "rewind": lambda: eng.rewind(1, list(range(1, 20)), 16),
        "export": lambda: eng.export_kv_chain(1),
        "import": lambda: eng.import_kv_chain({"geom": eng.kv_geometry(),
                                               "tokens": []}),
        "audit verify": lambda: eng.audit_step_args("verify"),
    }
    for what, call in refused.items():
        with pytest.raises(RecurrentStateUnsupported,
                           match="sliding-window latent layers") as e:
            call()
        assert "state snapshots" in str(e.value), what
    assert 2 not in eng.state_manager
    with pytest.raises(NotImplementedError, match="window latent layers"):
        jax.eval_shape(lambda: v2_model.ragged_forward_verify(
            eng.params, eng.cache_k, eng.cache_v,
            *([jnp.zeros((16,), jnp.int32)] * 4),
            jnp.zeros((5, 4), jnp.int32), jnp.zeros((5,), jnp.int32),
            jnp.zeros((5,), jnp.int32), cfg=eng.model_config, block_size=4))
    with pytest.raises(ValueError, match="kv_dtype='int8'"):
        engine(memory_config={"num_blocks": 16, "block_size": 4,
                              "kv_dtype": "int8"})


def test_the_server_refuses_the_three_options_and_serves():
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    eng = engine(budget=16)
    with pytest.raises(RecurrentStateUnsupported,
                       match="sliding-window latent layers"):
        InferenceServer(eng, {"prefix_cache": {"enabled": True}})
    with pytest.raises(RecurrentStateUnsupported,
                       match="sliding-window latent layers"):
        InferenceServer(eng, {}, spec_decoder=object())
    srv = InferenceServer(eng, {})
    with pytest.raises(RecurrentStateUnsupported,
                       match="sliding-window latent layers"):
        srv.submit([1, 2, 3], SamplingParams(max_new_tokens=2), handoff=True)
    srv.start()
    try:
        prompt = list(range(5, 40))
        stream = srv.submit(prompt, SamplingParams(max_new_tokens=5))
        toks = list(stream)
    finally:
        srv.stop(drain=False, timeout=30)
    want = reference_logits(eng, prompt + toks, last=6)
    assert toks == want[:-1].argmax(-1).tolist()


def test_the_training_forward_and_a_caller_without_rings_refuse():
    from deepspeed_tpu.inference.kv_generate import KVCachedGenerator

    model = get_model_config(PRESET)
    params = tf_model.init_params(model, jax.random.PRNGKey(0))
    assert set(params["layers"]) == {"full", "window", "mlp", "moe", "ln1",
                                     "ln2"}
    with pytest.raises(NotImplementedError, match="latent .MLA. attention"):
        tf_model.forward(params, jnp.zeros((1, 8), jnp.int32), model)
    with pytest.raises(ValueError, match="per-sequence rings"):
        KVCachedGenerator(model, block_size=8).generate(
            params, np.ones((1, 4), np.int32), 2)


def test_presets_hold_the_published_sizes():
    model = get_model_config("dots3-note-prev")
    m = model.mla
    assert (model.num_layers, model.hidden_size, model.vocab_size,
            model.intermediate_size) == (46, 5120, 152064, 13824)
    kinds = m.kinds(46)
    full = [i for i, (f, _) in enumerate(kinds) if f]
    assert full == [0, 1] + list(range(5, 46, 4)) and len(full) == 13
    assert [e for _, e in kinds] == [False] + [True] * 45
    assert (m.full.row_dim, m.window.row_dim, m.index_topk,
            m.sliding_window) == (576, 1088, 2048, 513)
    assert m.experts_held == (0, 256)
    share = get_model_config("dots3-note-prev-ep8", num_layers=5)
    assert share.mla.experts_held == (0, 32) and share.vocab_size == 19008
    shapes = jax.eval_shape(lambda k: tf_model.init_params(share, k),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 4_087_154_176
    assert (model.latent_row, model.window_row, model.experts_held,
            model.n_routed_experts) == (576, 1088, 256, 256)
    assert get_model_config("mistral-tiny").latent_row == 0


# -- models without latent attention run what the parent ran -----------------
def _parent_trunk(params, cache_k, cache_v, token_ids, token_slot, token_pos,
                  token_dest, block_tables, ctx_lens, state, cfg, block_size):
    """``model._ragged_trunk`` as the parent commit (PR 32) had it, for a
    model with one kind of layer: embedding, layout, one scan."""
    m = v2_model
    ssm_meta = m._ssm_meta(cfg, state, token_slot, token_pos)
    x = m._embed_rows(params, token_ids, token_pos, cfg)
    meta = m._step_meta(token_slot, token_pos, token_dest, block_tables,
                        ctx_lens, block_size, cache_k, cfg)

    def body(carry, scanned):
        h, ck, cv, ssm = carry
        lp, idx, conv = scanned
        st = ({"ssm": ssm, "conv": conv, "layer": idx} if cfg.ssm else None)
        h, ck, cv, st = m._ragged_layer(h, lp, ck, cv, idx, meta, cfg,
                                        layer_is_moe=False, state=st,
                                        ssm_meta=ssm_meta)
        if cfg.ssm:
            ssm, conv = st["ssm"], st["conv"]
        return (h, ck, cv, ssm), conv

    (x, cache_k, cache_v, ssm), conv = lax.scan(
        body, (x, cache_k, cache_v, state["ssm"] if cfg.ssm else None),
        (params["layers"], jnp.arange(0, cfg.num_layers),
         state["conv"] if cfg.ssm else None))
    x = m._norm(x, params["final_norm"], cfg)
    logits = m._lm_head(x, params, cfg)
    if cfg.ssm:
        logits = logits * cfg.ssm.lm_head_multiplier
    return logits.astype(jnp.float32)


@pytest.mark.parametrize("preset", ["mistral-tiny", "falcon-h1-tiny"])
def test_other_models_give_the_parents_logits_bit_for_bit(preset):
    model = get_model_config(preset)
    eng = InferenceEngineV2(model, {
        "dtype": "bfloat16", "memory_config": {"num_blocks": 32,
                                               "block_size": 4},
        "max_context": 64, "state_manager": {"max_tracked_sequences": 3,
                                             "max_ragged_batch_size": 16}},
        seed=1)
    rng = np.random.default_rng(0)
    t, s, nb = 16, 4, 4
    ids = jnp.asarray(rng.integers(0, 512, size=t), jnp.int32)
    slot = jnp.asarray([0] * 9 + [1] * 5 + [3] * 2, jnp.int32)
    pos = jnp.asarray(list(range(9)) + list(range(5)) + [0, 0], jnp.int32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0] * 4, [0] * 4],
                         jnp.int32)
    dest = jnp.where(slot < 3, tables[jnp.minimum(slot, 2), pos // 4] * 4
                     + pos % 4, 0)
    ctx = jnp.asarray([9, 5, 0, 0], jnp.int32)
    args = (ids, slot, pos, dest, tables, ctx)
    cfg = eng.model_config
    got = jax.jit(lambda p, k, v, st: v2_model.ragged_forward(
        p, k, v, *args, jnp.arange(s, dtype=jnp.int32) * 0 + 8, st, cfg=cfg,
        block_size=4)[0])(eng.params, eng.cache_k, eng.cache_v, eng.state)
    want = jax.jit(lambda p, k, v, st: _parent_trunk(
        p, k, v, *args, st, cfg, 4))(eng.params, eng.cache_k, eng.cache_v,
                                     eng.state)
    assert np.asarray(got).shape == (s, 512)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[8][None]
                                  .repeat(s, 0))


def test_the_configuration_file_builds_the_model_it_describes():
    from pathlib import Path

    from benchmark.lib.model import build_model

    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmark/configs/dots3-note-ep8-l5.json")
                     .read_text())
    model = build_model(cfg)
    want = reference_config(model)
    assert {k: cfg[k] for k in want} == dict(
        want, layer_types=cfg["layer_types"])
    assert cfg["layer_types"][:5] == want["layer_types"][:5]
    assert dataclasses.asdict(model.mla)["experts_held"] == (0, 32)
