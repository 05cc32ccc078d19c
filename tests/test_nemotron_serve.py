"""A one-mixer-a-layer model (``TransformerConfig.hybrid``: Nemotron-H, HF
``nemotron_h``) through ``InferenceEngineV2`` at the tiny preset, float32,
against the plain reference ``benchmark/reference/nemotron_h.py``: chunked
prefill then decoding through the cache with decode rows, a prompt's
middle chunk and a fresh prompt in ONE step; a slot reused from zeros; the
recurrent state's float32; the published head shapes (16 query heads to a
KV head, scan heads of 64 in 8 groups) through the XLA formulations and
the interpreted kernels; the ungated expert against a dense loop; the
shares of an expert layer against the uncut layer; what is refused by
name; the stages the lowered step names."""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import nemotron_h  # noqa: E402
from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2 import model as v2_model  # noqa: E402
from deepspeed_tpu.inference.v2.engine_v2 import \
    RecurrentStateUnsupported  # noqa: E402
from deepspeed_tpu.models import get_model_config  # noqa: E402
from deepspeed_tpu.models import transformer as tf_model  # noqa: E402
from deepspeed_tpu.moe.sharded_moe import moe_forward_held  # noqa: E402
from deepspeed_tpu.ops.pallas import paged_attention, ssd_ragged  # noqa: E402

ENGINE = {"dtype": "float32",
          "memory_config": {"num_blocks": 48, "block_size": 8},
          "max_context": 160,
          # ONE block-table width and one token bucket: one step program
          "state_manager": {"max_tracked_sequences": 4,
                            "max_ragged_batch_size": 16,
                            "min_context_blocks": 20}}
# float32 arithmetic on both sides: what is left is the order of the sums
# (the chunked scan against the recurrence, the experts' tiles against an
# expert at a time): 1e-5 of the logits' rms with room, where a wrong
# slot, a rotated key or a state rounded to bf16 reads 1e-3 and more
TOLERANCE = 2e-4


def reference_config(model) -> dict:
    """The published names ``reference/nemotron_h.py`` reads, from a
    model."""
    hy, m = model.hybrid, model.ssm
    return {"hidden_size": model.hidden_size,
            "num_hidden_layers": model.num_layers,
            "hybrid_override_pattern": hy.pattern,
            "num_attention_heads": model.num_heads,
            "num_key_value_heads": model.kv_heads,
            "head_dim": model.dim_per_head,
            "layer_norm_epsilon": model.layernorm_eps,
            "mamba_num_heads": m.num_heads, "mamba_head_dim": m.head_dim,
            "n_groups": m.n_groups, "ssm_state_size": m.state_size,
            "conv_kernel": m.conv_kernel, "use_conv_bias": m.conv_bias,
            "n_routed_experts": hy.experts_held[1],
            "experts_held_first": hy.experts_held[0],
            "num_experts_per_tok": hy.num_experts_per_tok,
            "routed_scaling_factor": hy.route_scale, "norm_topk_prob": True}


def seeded_bias(params, seed=5):
    """A non-zero selection bias (the seeded weights' is zeros), of the
    size of the scores at the edge of the choice (the seeded scores lie
    in the sigmoid's lower tail): it must move choices."""
    bias = params["layers"]["moe"]["bias"]
    noise = jax.random.normal(jax.random.PRNGKey(seed), bias.shape) * 1e-9
    return {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"], "bias": noise.astype(bias.dtype)}}}


def build(engine=None, **overrides):
    model = get_model_config("nemotron-h-tiny", param_dtype=jnp.float32,
                             **overrides)
    params = seeded_bias(tf_model.init_params(
        model.replace(dtype=jnp.float32), jax.random.PRNGKey(3)))
    return InferenceEngineV2(model, dict(engine or ENGINE),
                             model_params=params), model


def drive(eng, arrivals, decode):
    """Greedy streams through ``put``: ``arrivals`` {step: [(uid, prompt,
    priority)]}; every sequence decodes ``decode`` tokens after its
    prompt.  Returns ({uid: (logits rows, tokens)}, the steps' schedules
    as [(uid, cached before, rows)])."""
    rows, toks, steps = {}, {}, []
    plan = eng.scheduler.next_schedule

    def recorded(*a, **kw):
        schedule = plan(*a, **kw)
        steps.append([(seq.uid, seq.num_cached, n) for seq, n in schedule])
        return schedule
    eng.scheduler.next_schedule = recorded
    uids = {u for batch in arrivals.values() for u, _, _ in batch}
    done, step = set(), 0
    while done != uids:
        for uid, prompt, priority in arrivals.get(step, ()):
            eng.admit(uid, prompt, priority=priority)
            rows[uid], toks[uid] = [], []
        for uid, logits in eng.put([], []).items():
            rows[uid].append(np.asarray(logits, np.float32))
            if len(rows[uid]) > decode:
                eng.flush(uid)
                done.add(uid)
            else:
                toks[uid].append(int(logits.argmax()))
                eng.extend(uid, toks[uid][-1])
        step += 1
        assert step < 200
    eng.scheduler.next_schedule = plan
    return {u: (np.stack(rows[u]), toks[u]) for u in uids}, steps


def relative_rms(got, ref):
    return float(np.sqrt(((got - ref) ** 2).mean() / (ref ** 2).mean()))


def reference_rows(eng, model, prompt, toks):
    return np.asarray(nemotron_h.logits(
        eng.params, np.asarray([prompt + toks]), reference_config(model),
        jax.devices()[0], last=len(toks) + 1))[0]


def test_decode_rows_a_middle_chunk_and_a_fresh_prompt_in_one_step():
    """Sequence 1 decodes while sequence 2 (70 tokens, five chunks) is in
    the middle of its prompt and sequence 3 arrives ahead of it: one step
    holds a decode row, a whole fresh prompt and a middle chunk.  Each
    stream's logits equal the reference's one full forward pass."""
    eng, model = build()
    rng = np.random.default_rng(1)
    prompts = {1: rng.integers(0, 512, size=20).tolist(),
               2: rng.integers(0, 512, size=70).tolist(),
               3: rng.integers(0, 512, size=6).tolist()}
    out, steps = drive(eng, {0: [(1, prompts[1], 0)], 3: [(2, prompts[2], 0)],
                             5: [(3, prompts[3], 1)]}, decode=12)
    mixed = [s for s in steps
             if any(u == 1 and n == 1 for u, _, n in s)
             and any(u == 3 and c == 0 and n == 6 for u, c, n in s)
             and any(u == 2 and 0 < c and c + n < 70 for u, c, n in s)]
    assert mixed, steps
    for uid, (got, toks) in out.items():
        ref = reference_rows(eng, model, prompts[uid], toks)
        assert np.isfinite(got).all()
        assert relative_rms(got, ref) < TOLERANCE, uid
        assert (got.argmax(-1) == ref.argmax(-1)).all()
    # every page and every slot came back
    assert eng.free_blocks == 47 and eng.state_manager.n_active == 0


def test_a_reused_slot_starts_from_zeros():
    """A sequence that gets the slot another left reads as it does in an
    engine of its own: a run from position 0 starts from zeros, whatever
    the slot holds (state and convolution tails alike)."""
    eng, model = build()
    rng = np.random.default_rng(2)
    first = rng.integers(0, 512, size=30).tolist()
    second = rng.integers(0, 512, size=19).tolist()
    drive(eng, {0: [(1, first, 0)]}, decode=3)
    slot = eng.state_manager._free_slots[-1]        # the next handed out
    assert np.abs(np.asarray(eng.state["ssm"][:, slot])).max() > 0
    assert np.abs(np.asarray(eng.state["conv"]).reshape(4, 8, -1)[
        :, slot]).max() > 0                         # the slot is dirty
    eng.admit(2, second)
    assert eng.state_manager.get(2).slot == slot
    eng.flush(2)
    again, _ = drive(eng, {0: [(2, second, 0)]}, decode=3)
    fresh, _ = drive(build()[0], {0: [(2, second, 0)]}, decode=3)
    np.testing.assert_array_equal(again[2][0], fresh[2][0])
    ref = reference_rows(eng, model, second, again[2][1])
    assert relative_rms(again[2][0], ref) < TOLERANCE


def test_the_recurrent_state_is_float32_and_bf16_reads_larger():
    """The slots are float32; rounded to bf16 after every step (what a
    bf16 slot would hand from one step to the next) the same stream reads
    an order of magnitude further from the reference."""
    eng, model = build()
    assert eng.state["ssm"].dtype == jnp.float32
    assert eng.state["ssm"].shape == (4, 5, 8, 8, 16)
    # a sequence's tails of a layer are one row; 5 slots made up to 8
    assert eng.state["conv"].shape == (4 * 8, 3 * (64 + 2 * 2 * 16))
    prompt = np.random.default_rng(3).integers(0, 512, size=40).tolist()
    exact, _ = drive(eng, {0: [(1, prompt, 0)]}, decode=16)
    eng, _ = build()
    carried = eng._carried

    def through_bf16(out):
        out = carried(out)
        eng.state["ssm"] = eng.state["ssm"].astype(jnp.bfloat16).astype(
            jnp.float32)
        return out
    eng._carried = through_bf16
    rounded, _ = drive(eng, {0: [(1, prompt, 0)]}, decode=16)
    ref = reference_rows(eng, model, prompt, exact[1][1])
    assert exact[1][1] == rounded[1][1]
    err = relative_rms(exact[1][0], ref)
    assert err < TOLERANCE
    assert relative_rms(rounded[1][0], ref) > 10 * err


@pytest.fixture
def interpreted():
    was = paged_attention.INTERPRET, ssd_ragged.INTERPRET
    paged_attention.INTERPRET = ssd_ragged.INTERPRET = True
    yield
    paged_attention.INTERPRET, ssd_ragged.INTERPRET = was


def test_sixteen_query_heads_to_a_kv_head(interpreted):
    """32 query heads over 2 KV heads of 128 (no cell had more than 6 to
    one): a decode row beside a chunk, through the XLA gather and through
    the interpreted ``paged_qblock``, against a dense causal softmax."""
    nh, nkv, d, bs = 32, 2, 128, 16
    lens, new = (37, 21), (1, 12)           # context after the step, rows
    rng = np.random.default_rng(4)
    k_all = rng.standard_normal((2, 48, nkv, d)).astype(np.float32)
    v_all = rng.standard_normal((2, 48, nkv, d)).astype(np.float32)
    # sequence s holds pages 1 + 3 s .. of a pool of 8 pages
    tables = np.array([[1, 2, 3], [4, 5, 6], [0, 0, 0]], np.int32)
    pool_k = np.zeros((nkv, 8 * bs, d), np.float32)
    pool_v = np.zeros_like(pool_k)
    slot, pos = [], []
    for s, (n, m) in enumerate(zip(lens, new)):
        rows = tables[s][np.arange(n) // bs] * bs + np.arange(n) % bs
        pool_k[:, rows] = k_all[s, :n].swapaxes(0, 1)
        pool_v[:, rows] = v_all[s, :n].swapaxes(0, 1)
        slot += [s] * m
        pos += list(range(n - m, n))
    t = len(slot)
    q = rng.standard_normal((t, nh, d)).astype(np.float32)
    slot, pos = np.array(slot, np.int32), np.array(pos, np.int32)
    clen = np.array(lens, np.int32)[slot]
    want = np.zeros((t, nh, d), np.float32)
    for i in range(t):
        keys = k_all[slot[i], :pos[i] + 1]              # [c, nkv, d]
        vals = v_all[slot[i], :pos[i] + 1]
        for h in range(nh):
            sc = keys[:, h // 16] @ q[i, h] / np.sqrt(d)
            p = np.exp(sc - sc.max())
            want[i, h] = (p / p.sum()) @ vals[:, h // 16]
    cfg = get_model_config("nemotron-3-nano-30b-a3b-ep2",
                           dtype=jnp.float32)
    ctx = np.arange(3 * bs)
    gather = (tables[:, ctx // bs] * bs + ctx % bs)[slot]
    with jax.default_matmul_precision("highest"):
        xla = v2_model._paged_attention_xla(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(gather), jnp.asarray(pos), jnp.asarray(clen), cfg)
        # (past the jit wrapper, so that INTERPRET is read whatever an
        # earlier test traced)
        kernel = paged_attention.paged_decode_attention.__wrapped__(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(clen), bs,
            d ** -0.5, token_slot=jnp.asarray(slot))
    np.testing.assert_allclose(np.asarray(xla), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kernel), want, rtol=2e-4,
                               atol=2e-5)


def test_scan_heads_of_64_in_8_groups(interpreted):
    """64 heads of 64, 8 groups, state 128 (falcon: heads of 128, 2
    groups, state 256): two decode rows, a run that crosses a chunk
    boundary from zeros and one that starts from its slot, through the
    XLA formulation and the interpreted ``ssd_ragged`` kernel, against
    the recurrence written out; the state comes back in its slots."""
    h, p, g, n, chunk = 64, 64, 8, 128, 16
    slot = np.array([0, 1] + [2] * 20 + [3] * 5 + [4] * 5, np.int32)
    pos = np.array([9, 4] + list(range(20)) + list(range(7, 12)) + [0] * 5,
                   np.int32)            # slot 4 is the pad's
    t = len(slot)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((t, h, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.standard_normal((t, h)))).astype(np.float32) / 4
    a = -np.exp(rng.uniform(0, 2.5, h)).astype(np.float32)
    b = rng.standard_normal((t, g, n)).astype(np.float32) / 4
    c = rng.standard_normal((t, g, n)).astype(np.float32) / 4
    state = rng.standard_normal((2, 5, h, p, n)).astype(np.float32)
    want_y = np.zeros((t, h, p), np.float32)
    want_s = state[1].copy()
    cur = {}
    for i in range(t):
        s = slot[i]
        if s == 4:
            continue
        if s not in cur:
            cur[s] = np.zeros((h, p, n), np.float32) if pos[i] == 0 \
                else state[1, s].copy()
        bh, ch = np.repeat(b[i], h // g, 0), np.repeat(c[i], h // g, 0)
        cur[s] = np.exp(dt[i] * a)[:, None, None] * cur[s] \
            + (dt[i][:, None] * x[i])[:, :, None] * bh[:, None, :]
        want_y[i] = np.einsum("hpn,hn->hp", cur[s], ch)
        want_s[s] = cur[s]
    args = [jnp.asarray(v) for v in (x, dt, a, b, c, state, slot, pos)]
    with jax.default_matmul_precision("highest"):
        for impl in ("xla", "pallas"):
            y, new = ssd_ragged.ssd_ragged(*args, layer=1, impl=impl,
                                           chunk=chunk)
            real = slot != 4
            np.testing.assert_allclose(np.asarray(y)[real], want_y[real],
                                       rtol=2e-4, atol=2e-4, err_msg=impl)
            np.testing.assert_allclose(np.asarray(new[1, :4]), want_s[:4],
                                       rtol=2e-4, atol=2e-4, err_msg=impl)
            np.testing.assert_array_equal(np.asarray(new[0]), state[0])


def _moe(model, key=4):
    return seeded_bias(tf_model.init_params(
        model, jax.random.PRNGKey(key)))["layers"]["moe"]


def test_the_ungated_expert_against_a_dense_loop():
    """``moe_forward_held`` with experts of TWO matrices: each row's held
    chosen experts ``relu(x W_u^T)^2 W_o``, weighted and scaled, written
    as a loop over every (row, expert)."""
    model = get_model_config("nemotron-h-tiny", param_dtype=jnp.float32,
                             dtype=jnp.float32)
    moe, hy = _moe(model), model.hybrid
    assert "wg" not in moe and moe["wu"].shape == (4, 8, 32, 64)
    x = jax.random.normal(jax.random.PRNGKey(9), (40, 64)) + 0.5
    layer, first = 2, hy.experts_held[0]
    with jax.default_matmul_precision("highest"):
        got = moe_forward_held(x, moe, layer, top_k=hy.num_experts_per_tok,
                               first=first, scale=hy.route_scale)
    s = jax.nn.sigmoid(np.asarray(x) @ np.asarray(moe["router"][layer]))
    s = np.asarray(s, np.float64)
    order = np.argsort(-(s + np.asarray(moe["bias"][layer])), -1)[:, :3]
    want = np.zeros((40, 64))
    held_pairs = 0
    for r in range(40):
        w = s[r, order[r]] / s[r, order[r]].sum() * hy.route_scale
        for e, we in zip(order[r], w):
            if first <= e < first + 8:
                held_pairs += 1
                up = np.asarray(moe["wu"][layer, e - first], np.float64)
                down = np.asarray(moe["wo"][layer, e - first], np.float64)
                hidden = np.maximum(np.asarray(x[r], np.float64) @ up.T, 0)
                want[r] += we * (hidden ** 2) @ down
    assert 20 < held_pairs < 100
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_shares_add_up_to_the_uncut_layer():
    """The shares test: the routed parts of shares (0, 8) and (8, 8) of
    the tiny preset's 16 experts, each through the PROGRAM's held layer
    with the router over all 16, and the shared expert counted once, add
    up to the uncut reference's whole ``E`` layer."""
    model = get_model_config("nemotron-h-tiny", param_dtype=jnp.float32,
                             dtype=jnp.float32)
    hy = model.hybrid
    whole = model.replace(hybrid=tf_model.dataclasses.replace(
        hy, experts_held=(0, 16)))
    moe = _moe(whole)
    u = jax.random.normal(jax.random.PRNGKey(9), (48, 64)) + 0.5
    layer = 1
    shared = {k: v[layer] for k, v in moe["shared"].items()}
    with jax.default_matmul_precision("highest"):
        total = jnp.square(jax.nn.relu(u @ shared["wi"])) @ shared["wo"]
        for first in (0, 8):
            share = {**moe, **{n: moe[n][:, first:first + 8]
                               for n in ("wu", "wo")}}
            part = moe_forward_held(u, share, layer, first=first,
                                    top_k=hy.num_experts_per_tok,
                                    scale=hy.route_scale)
            assert jnp.abs(part).sum() > 0
            total = total + part
        cfg = dict(reference_config(whole))
        assert (cfg["n_routed_experts"], cfg["experts_held_first"]) == (16, 0)
        ref = nemotron_h.expert_layer(cfg, jax.devices()[0])(u[None], moe,
                                                             layer)[0]
    # (squares double a relative error, and a sum of three cancels)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _server(eng, config, **kw):
    from deepspeed_tpu.serving import InferenceServer

    return InferenceServer(eng, config, **kw)


REFUSED = {
    "prefix adoption": lambda eng: eng.admit(
        9, list(range(20)), cached_blocks=[1], num_cached=8),
    "verify_step": lambda eng: eng.verify_step({7: [1, 2]}),
    "rewind": lambda eng: eng.rewind(7, [1, 2, 3], 2),
    "export": lambda eng: eng.export_kv_chain(7),
    "import": lambda eng: eng.import_kv_chain({"geom": (), "tokens": []}),
    "audit of the verify step": lambda eng: eng.audit_step_args("verify"),
    "server: prefix cache": lambda eng: _server(
        eng, {"prefix_cache": {"enabled": True}}),
    "server: spec decoder": lambda eng: _server(eng, {},
                                                spec_decoder=object()),
    "server: hand-off": lambda eng: _server(eng, {}).submit(
        [1, 2, 3], handoff=True),
}


@pytest.fixture(scope="module")
def stepped():
    """One engine with a live sequence (a refusal leaves it as it was)."""
    eng, _ = build()
    eng.admit(7, list(range(30)))
    eng.step()
    return eng


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_needs_a_state_snapshot_is_refused_by_name(what, stepped):
    with pytest.raises(RecurrentStateUnsupported, match="recurrent state"):
        REFUSED[what](stepped)
    assert stepped.state_manager.n_active == 1


def test_training_and_stateless_callers_refuse_the_model():
    model = get_model_config("nemotron-h-tiny")
    for what in (lambda: tf_model.forward(
            None, jnp.zeros((1, 4), jnp.int32), model),
            lambda: tf_model.transformer_layer(None, None, None, model)):
        with pytest.raises(NotImplementedError, match="ONE mixer a layer"):
            what()
    with pytest.raises(NotImplementedError, match="MEMEM\\*EME"):
        tf_model.refuse_ssm(model, "ds.initialize")
    with pytest.raises(NotImplementedError, match="state snapshots"):
        v2_model.ragged_forward_verify(
            None, None, None, *([None] * 7), cfg=model, block_size=8)
    with pytest.raises(ValueError, match="per-sequence state slots"):
        v2_model.ragged_forward(
            {"layers": {}, "embed": {}}, jnp.zeros((1,)), None,
            *([None] * 7), cfg=model, block_size=8)


def test_generate_and_server_streams_agree():
    """The server's streams under load equal ``generate()``'s (the fused
    decode loop among its programs), greedy."""
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, size=n).tolist()
               for n in (50, 9, 33, 70, 21)]
    eng, _ = build()
    want = eng.generate(prompts, max_new_tokens=20)
    eng, _ = build()
    srv = InferenceServer(eng, {})
    srv.start()
    try:
        streams = [srv.submit(p, SamplingParams(max_new_tokens=20))
                   for p in prompts]
        got = [list(s) for s in streams]
    finally:
        srv.stop(drain=False, timeout=30)
    assert got == want
    assert eng.free_blocks == 47 and eng.state_manager.n_active == 0


def test_the_step_names_the_stages_the_serving_step_has():
    """No new stage name: the lowered step names a mixer's stages, the
    attention's and the held experts', and nothing else (no ``mlp``: no
    layer has a feed-forward beside its mixer)."""
    from deepspeed_tpu.utils import xplane

    eng, _ = build()
    fn, args = eng.audit_step_args("decode")
    stacks = set(re.findall(r'loc\("([^"]+)"',
                            fn.lower(*args).as_text(debug_info=True)))
    assert {xplane._stage_of(s) for s in stacks} - {xplane._UNSCOPED} == {
        "embed", "layers", "head", "ssm.in", "ssm.conv", "ssm.scan",
        "ssm.out", "attn.qkv", "attn.append", "attn.read", "attn.out",
        "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
        "moe.shared"}


def test_the_layer_walk_and_the_registry():
    """Nine layers walk as one scan of two (M, E) and five single layers;
    the published preset's widths; every kind's stack sized by its own
    layers."""
    model = get_model_config("nemotron-h-tiny")
    assert v2_model.layer_segments(list(model.hybrid.kinds(9))) == [
        (0, 2, 2), (4, 1, 1), (5, 1, 1), (6, 1, 1), (7, 1, 1), (8, 1, 1)]
    full = get_model_config("nemotron-3-nano-30b-a3b")
    kinds = full.layer_kinds
    assert (len(kinds), kinds.count("M"), kinds.count("*"),
            kinds.count("E")) == (52, 23, 6, 23)
    assert (full.ssm_layers, full.attn_layers, full.expert_layers) == (
        23, 6, 23)
    assert (full.ssm.d_ssm, full.ssm.conv_dim, full.ssm.proj_dim) == (
        4096, 6144, 10304)
    ep2 = get_model_config("nemotron-3-nano-30b-a3b-ep2", num_layers=9)
    assert (ep2.layer_kinds, ep2.vocab_size, ep2.hybrid.experts_held) == (
        "MEMEM*EME", 65536, (0, 64))
    # a model of another kind keeps its sizes
    falcon = get_model_config("falcon-h1-tiny")
    assert (falcon.ssm_layers, falcon.attn_layers, falcon.layer_kinds) == (
        2, 2, "")
    state = jax.eval_shape(lambda: v2_model.new_ssm_state(falcon, 4))
    assert state["conv"].shape == (2, 5, 3, falcon.ssm.conv_dim)
