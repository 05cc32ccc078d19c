"""GLM-5 on the serving path (``glm-5-tiny``, float32): every layer a
full latent layer with the learned indexer, no gate, interleaved rotary
pairs, experts scaled by 2.5, and SELF-DRAFTING through the model's
multi-token-prediction module inside the one ragged step: the trunk and
the module against the benchmark's plain reference, self-drafted streams
against plain greedy streams token for token, the position a refused
draft gives back, the expert shares, the rotary layouts, the step's
spans, and the refusals that still apply (and those that no longer do)."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, latent
from deepspeed_tpu.inference.v2 import model as v2_model
from deepspeed_tpu.inference.v2.engine_v2 import RecurrentStateUnsupported
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.models import transformer as tf_model

reference = importlib.import_module("benchmark.reference.glm_moe_dsa")

PRESET = "glm-5-tiny"


def reference_config(model):
    """The published names ``benchmark/reference/glm_moe_dsa.py`` reads,
    from the program's own configuration."""
    m, w = model.mla, model.mla.full
    return {"hidden_size": model.hidden_size,
            "rms_norm_eps": model.layernorm_eps,
            "num_hidden_layers": model.num_layers,
            "first_k_dense_replace": m.first_k_dense,
            "num_attention_heads": w.num_heads,
            "q_lora_rank": w.q_lora_rank, "kv_lora_rank": w.kv_lora_rank,
            "qk_nope_head_dim": w.qk_nope_head_dim,
            "qk_rope_head_dim": w.qk_rope_head_dim,
            "v_head_dim": w.v_head_dim,
            "rope_parameters": {"rope_theta": w.rope_theta},
            "rope_interleave": m.rope_interleaved,
            "indexer_rope_interleave": m.rope_interleaved,
            "index_topk": m.index_topk, "index_n_heads": m.index_heads,
            "index_head_dim": m.index_head_dim,
            "num_experts_per_tok": m.num_experts_per_tok,
            "n_routed_experts": m.experts_held[1],
            "experts_held_first": m.experts_held[0],
            "routed_scaling_factor": m.routed_scaling_factor,
            "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
            "num_nextn_predict_layers": m.mtp_layers}


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
    """A selection bias large enough to change choices, in the trunk's
    expert layers and in the module's."""
    for k, moe in enumerate((eng.params["layers"]["moe"],
                             eng.params["mtp"]["moe"])):
        moe["bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(seed + k), moe["bias"].shape)


def ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, size=n).tolist()


def put_stream(eng, uid, prompt, decode):
    """Prefill in chunks, then ``decode`` greedy tokens through ``put``:
    (logit rows, tokens)."""
    out = eng.put([uid], [prompt])
    while uid not in out:
        out = eng.put([], [])
    rows, toks = [np.asarray(out[uid])], []
    for _ in range(decode):
        toks.append(int(rows[-1].argmax()))
        eng.extend(uid, toks[-1])
        rows.append(np.asarray(eng.put([], [])[uid]))
    return np.stack(rows), toks


def drafted(eng, prompts, n_new, arrive=None, preempt=None):
    """Serve ``prompts`` {uid: tokens} through self-drafting steps until
    each has ``n_new`` tokens; ``arrive`` {uid: step} admits later,
    ``preempt`` (uid, step) evicts a sequence and readmits what it
    knows.  Returns ({uid: tokens}, the steps' results)."""
    arrive = arrive or {}
    waiting = dict(prompts)
    out = {u: [] for u in prompts}
    log = []
    step = 0
    while any(len(t) < n_new for t in out.values()):
        for u in [u for u in waiting if arrive.get(u, 0) <= step]:
            eng.admit(u, waiting.pop(u))
        if preempt and preempt[1] == step:
            known = eng.preempt(preempt[0])
            # what it knows: the prompt and every token delivered
            assert known == prompts[preempt[0]] + out[preempt[0]]
            eng.admit(preempt[0], known)
        res = eng.step_bursts()
        log.append(res)
        for u, burst in res.items():
            out[u].extend(burst)
            if len(out[u]) >= n_new:
                eng.flush(u)
            else:
                eng.extend(u, burst[-1])
        step += 1
        assert step < 40 * n_new
    return {u: t[:n_new] for u, t in out.items()}, log


# -- the trunk and the module against the reference ----------------------
@pytest.mark.parametrize("prompt,budget,block_size", [(40, 16, 4),
                                                      (75, 32, 8)])
def test_prefill_in_chunks_then_decode_is_the_reference(prompt, budget,
                                                        block_size):
    """Contexts far past ``index_topk`` (8): the selection is active."""
    eng = engine(budget=budget, block_size=block_size)
    nonzero_bias(eng)
    assert eng.state is None and eng.cache_k.shape[0] == 5
    tokens = ids(prompt)
    got, toks = put_stream(eng, 7, tokens, decode=6)
    want = np.asarray(reference.logits(
        eng.params, np.asarray([tokens + toks]),
        reference_config(eng.model_config), jax.devices()[0], last=7))[0]
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).all()


def module_logits(eng, tokens, chunks):
    """The module's logits at every position but the last, through the
    program's own pieces (``latent_trunk``, ``mtp_rows``) and the pages,
    the sequence fed in ``chunks`` rows at a time, teacher-forced."""
    cfg, bs = eng.model_config, eng.cfg.block_size
    n = len(tokens)
    table = jnp.arange(1, 1 + -(-n // bs), dtype=jnp.int32)[None]

    @jax.jit
    def chunk(params, ck, cv, ids, following, pos, end):
        slot = jnp.zeros_like(pos)
        dest = table[0, pos // bs] * bs + pos % bs
        args = (slot, pos, dest, table, end)
        x, ck, cv, _ = latent.latent_trunk(params, ck, cv, ids, *args, None,
                                           cfg, bs)
        hidden, ck, cv = latent.mtp_rows(params, x, following, ck, cv,
                                         *args, cfg, bs)
        return hidden @ params["lm_head"], ck, cv

    ck, cv = eng.cache_k, eng.cache_v
    rows = []
    for start in range(0, n - 1, chunks):
        end = min(start + chunks, n - 1)
        out, ck, cv = chunk(
            eng.params, ck, cv, jnp.asarray(tokens[start:end]),
            jnp.asarray(tokens[start + 1:end + 1]),
            jnp.arange(start, end, dtype=jnp.int32),
            jnp.asarray([end], jnp.int32))
        rows.append(out)
    return np.concatenate(rows)


def test_the_modules_logits_are_the_references_draft_logits():
    eng = engine(blocks=64)
    nonzero_bias(eng)
    tokens = ids(45, seed=4)
    want = np.asarray(reference.draft_logits(
        eng.params, np.asarray([tokens]), reference_config(eng.model_config),
        jax.devices()[0]))[0]
    assert want.shape == (44, 512)
    for chunks in (64, 13):
        got = module_logits(eng, tokens, chunks)
        np.testing.assert_allclose(got, want,
                                   atol=1e-4 * np.abs(want).max())
    # and it is no copy of the trunk's
    trunk = np.asarray(reference.logits(
        eng.params, np.asarray([tokens]), reference_config(eng.model_config),
        jax.devices()[0]))[0]
    assert np.abs(trunk[1:] - want).max() > 0.1 * np.abs(want).max()


# -- self-drafted streams are plain greedy streams -----------------------
PROMPTS = {0: ids(20, 1), 1: ids(7, 2), 2: ids(33, 3)}


@pytest.fixture(scope="module")
def plain_streams():
    return dict(zip(PROMPTS, engine().generate(list(PROMPTS.values()),
                                               max_new_tokens=40)))


def test_self_drafted_streams_are_plain_greedy_streams(plain_streams):
    """Prompts arrive while others decode (chunks and verify runs share
    steps under the one budget), one sequence is preempted and readmitted
    mid-stream; some drafts stand and some are refused."""
    eng = engine(self_draft=True)
    got, log = drafted(eng, PROMPTS, 40, arrive={1: 3, 2: 6},
                       preempt=(0, 12))
    assert got == plain_streams
    assert 0 < eng.drafts_accepted < eng.drafts_verified
    assert any(len(b) == 2 for res in log for b in res.values())
    # a step held a prompt's chunk beside verify runs
    mixed = [res for res in log if any(len(b) == 2 for b in res.values())]
    assert mixed and eng.state_manager.n_active == 0
    assert eng.free_blocks == eng.cfg.num_blocks - 1


def test_the_plain_calling_sequence_serves_a_drafting_engine(plain_streams):
    """``step``, ``extend``, ``flush`` as a caller of a plain engine makes
    them (the benchmark's warm-up does): the value is one token, the LAST
    the step delivered, and the caller's ``extend`` appends it; a burst's
    first token is in the sequence and not in the value."""
    eng = engine(self_draft=True)
    for u, p in PROMPTS.items():
        eng.admit(u, p)
    seen = {u: 0 for u in PROMPTS}
    for _ in range(30):
        for u, tok in eng.step(temperature=0.0).items():
            assert isinstance(tok, int)
            seq = eng.state_manager.get(u)
            assert seq.uncached == 0
            eng.extend(u, tok)
            assert seq.uncached == 1
            seen[u] += 1
    assert eng.drafts_accepted > 0
    for u, p in PROMPTS.items():
        stream = eng.state_manager.get(u).tokens[len(p):]
        assert len(stream) > seen[u]        # some step delivered two
        known = plain_streams[u][:len(stream)]
        assert stream[:len(known)] == known
    plain = engine()
    with pytest.raises(ValueError, match="self_draft"):
        plain.step_bursts()


@pytest.mark.parametrize("read", ["latent_read_gather", "latent_read_walk"])
def test_a_refused_drafts_position_is_rewritten_before_it_is_read(
        plain_streams, read, monkeypatch):
    """After every step the cache rows, the trunk's and the module's, at
    and past each sequence's next position are overwritten with NaN: a
    refused draft's rows lie there.  The streams do not change, whether
    the full layers gather their rows or walk the pages (the kernel,
    interpreted)."""
    from deepspeed_tpu.ops.pallas import latent_read

    monkeypatch.setattr(latent_read, "INTERPRET", True)
    eng = engine(self_draft=True, modules={"latent_read": read})
    bs = eng.cfg.block_size
    for u, p in PROMPTS.items():
        eng.admit(u, p)
    out = {u: [] for u in PROMPTS}
    refused = 0
    while any(len(t) < 40 for t in out.values()):
        before = eng.drafts_verified - eng.drafts_accepted
        for u, burst in eng.step_bursts().items():
            out[u].extend(burst)
            eng.extend(u, burst[-1])
        refused += eng.drafts_verified - eng.drafts_accepted - before
        rows = []
        for u in PROMPTS:
            seq = eng.state_manager.get(u)
            for pos in range(seq.num_cached, len(seq.blocks) * bs):
                rows.append(seq.blocks[pos // bs] * bs + pos % bs)
        at = jnp.asarray(rows, jnp.int32)
        eng.cache_k = eng.cache_k.at[:, at].set(jnp.nan)
        eng.cache_v = eng.cache_v.at[:, at].set(jnp.nan)
    assert refused > 3
    assert {u: t[:40] for u, t in out.items()} == plain_streams


def ahead(eng, prompts, n_new, arrive=None, poison=False):
    """Serve ``prompts`` through ``launch`` / ``fetch`` with every step
    launched before the step before it is fetched, the place of each
    token in flight kept with ``IN_FLIGHT``.  ``poison``: between two
    launches every cache row at and past a sequence's next position is
    overwritten with NaN, and the row of the draft the unfetched step
    verifies too WHERE THAT STEP REFUSES IT (decided on the device: the
    host does not know yet).  Returns ({uid: tokens}, steps, verify runs
    launched ahead of the step that ran their prompt's last chunk)."""
    from deepspeed_tpu.inference.v2.ragged import IN_FLIGHT

    arrive = arrive or {}
    waiting = dict(prompts)
    out = {u: [] for u in prompts}
    mgr, bs = eng.state_manager, eng.cfg.block_size
    flight, steps, first_runs = None, 0, 0
    while waiting or mgr.n_active:
        for u in [u for u in waiting if arrive.get(u, 0) <= steps]:
            eng.admit(u, waiting.pop(u))
        nxt = eng.launch()
        if flight is not None:
            if nxt is not None:
                first_runs += len(nxt.verified & flight.uids
                                  - flight.verified)
            for u, burst in eng.fetch(flight).items():
                if u in mgr:
                    out[u].extend(burst)
                    if len(out[u]) >= n_new:
                        eng.flush(u)
        flight = nxt
        steps += 1
        assert steps < 40 * n_new
        if flight is None:
            continue
        for u in flight.uids:
            if u in mgr:
                eng.extend(u, IN_FLIGHT)
        if poison:
            dead, maybe, slots = [], [], []
            for u in prompts:
                if u not in mgr:
                    continue
                seq = mgr.get(u)
                # with the step in flight unknown the sequence stands as
                # if its draft were refused: its position given back
                nxt_pos = seq.num_cached + (seq.draft == IN_FLIGHT
                                            and u in flight.verified)
                for pos in range(nxt_pos, len(seq.blocks) * bs):
                    dead.append(seq.blocks[pos // bs] * bs + pos % bs)
                if u in flight.verified:
                    pos = nxt_pos - 1       # the draft in flight
                    maybe.append(seq.blocks[pos // bs] * bs + pos % bs)
                    slots.append(seq.slot)
            refused = (flight.out[2, jnp.asarray(slots, jnp.int32)]
                       == 0)[None, :, None]
            dead = jnp.asarray(dead, jnp.int32)
            maybe = jnp.asarray(maybe, jnp.int32)

            def nan(cache):
                cache = cache.at[:, dead].set(jnp.nan)
                return cache.at[:, maybe].set(
                    jnp.where(refused, jnp.nan, cache[:, maybe]))

            eng.cache_k, eng.cache_v = nan(eng.cache_k), nan(eng.cache_v)
    if flight is not None:
        eng.fetch(flight)       # dead rows of sequences flushed since
    return {u: t[:n_new] for u, t in out.items()}, steps, first_runs


# what the module's drafts come to: as initialised (some stand), with the
# module's last norm zeroed (its logits are flat, its draft token 0: none
# stands), with the trunk's zeroed too (every token is 0: all stand)
_DRAFTS = {"mixed": (), "all_refused": ("mtp",),
           "all_accepted": ("mtp", "trunk")}


def test_steps_launched_ahead_deliver_plain_greedy_streams(plain_streams):
    """At least 40 steps a case, each launched before the one before it
    is fetched: verify runs written for a refused draft and moved on the
    device where it stood, prompts that arrive meanwhile (a last chunk
    followed ahead by its first verify run), sequences that sit a launch
    out at a page's edge.  The streams are plain greedy decoding's and
    ``step_bursts``' on the same engine, refused positions rewritten
    before they are read; ONE engine and its programs for all three
    kinds of draft (the weights are operands)."""
    eng = engine(self_draft=True)
    norms = {"mtp": eng.params["mtp"]["norm"],
             "trunk": eng.params["final_norm"]}
    arrive = {1: 9, 2: 18}
    programs = None
    for drafts, zeroed in _DRAFTS.items():
        for name in zeroed:
            norms[name]["scale"] = jnp.zeros_like(norms[name]["scale"])
        n_new = 90 if "trunk" in zeroed else 40
        want = ({u: [0] * n_new for u in PROMPTS} if "trunk" in zeroed
                else plain_streams)
        seen = eng.drafts_verified, eng.drafts_accepted
        got, steps, first_runs = ahead(eng, PROMPTS, n_new, arrive,
                                       poison="trunk" not in zeroed)
        assert got == want, drafts
        assert steps >= 40 and first_runs >= 2, drafts
        verified = eng.drafts_verified - seen[0]
        accepted = eng.drafts_accepted - seen[1]
        if drafts == "mixed":
            assert 0 < accepted < verified
        elif drafts == "all_accepted":
            assert accepted == verified > 80
        else:
            # (a flat module drafts token 0: a stream's own 0 stands)
            assert accepted <= sum(t.count(0) for t in want.values()) + 1
        assert eng.state_manager.n_active == 0 and eng._flight is None
        assert eng.free_blocks == eng.cfg.num_blocks - 1
        # the same programs with each step fetched before the next
        programs = programs or set(eng._dispatched)
        behind, _ = drafted(eng, PROMPTS, n_new, arrive=arrive)
        assert behind == want and eng._dispatched == programs, drafts
    assert eng._draft._cache_size() == len(programs)


def test_the_server_delivers_bursts_and_cuts_them_at_max_new_tokens(
        plain_streams):
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    eng = engine(self_draft=True)
    srv = InferenceServer(eng, {"tracing": {"enabled": True}})
    srv.start()
    try:
        # every length from 1 up: some end inside a burst of two
        streams = {(u, n): srv.submit(PROMPTS[u],
                                      SamplingParams(max_new_tokens=n))
                   for u in PROMPTS for n in (1, 2, 5, 8, 9)}
        got = {k: list(s) for k, s in streams.items()}
    finally:
        # (the loop fetches the step it launched behind the last burst)
        srv.stop(timeout=60)
    for (u, n), toks in got.items():
        assert toks == plain_streams[u][:n], (u, n)
    snap = srv.metrics.snapshot()
    assert snap["steps_ahead"] > 0
    assert snap["spec_proposed"] == eng.drafts_verified > 0
    assert snap["spec_accepted"] == eng.drafts_accepted > 0
    assert eng.state_manager.n_active == 0
    spans = [e for e in srv.tracer.snapshot() if e.get("ph") == "X"]
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e["args"])
    # one transfer, one program, one fetch a step; the fetch knows the
    # accepted count, the schedule the verify runs and the module's rows
    assert all(a["arrays"] == 1 for a in by["v2.h2d"])
    assert all(a["programs"] == 1 for a in by["v2.dispatch"])
    assert len(by["v2.h2d"]) == len(by["v2.dispatch"]) == len(by["v2.fetch"])
    assert sum(a["drafts"] for a in by["v2.fetch"]) == eng.drafts_verified
    assert sum(a["accepted"] for a in by["v2.fetch"]) == eng.drafts_accepted
    ran = [a for a in by["v2.schedule"] if a.get("tokens")]
    assert sum(a["verify_runs"] for a in ran) == eng.drafts_verified
    # runs left to the device: on the steps launched ahead and no other
    assert sum(a["ahead_runs"] for a in ran) > 0
    assert all(a["ahead_runs"] <= a["seqs"] for a in ran)
    assert sum(a["ahead"] for a in by["v2.dispatch"]) \
        == snap["steps_ahead"] >= sum(a["ahead_runs"] > 0 for a in ran)
    assert all(a["mtp_rows"] == a["tokens"] and a["draft_rows"]
               == a["verify_runs"] for a in ran)
    # verify runs and prefill chunks in the same steps
    assert any(a["verify_runs"] and a["prefill_tokens"] > 2
               * a["verify_runs"] for a in ran)


def test_a_step_without_the_module_ends_a_sequences_drafting(plain_streams):
    """``put`` (what the benchmark's gate calls) runs the trunk alone: the
    sequence is served on, one row a step, and drafts no more; the logits
    it returns are the plain engine's."""
    eng, plain = engine(self_draft=True), engine()
    got, toks = put_stream(eng, 5, PROMPTS[0], decode=4)
    want, _ = put_stream(plain, 5, PROMPTS[0], decode=4)
    np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max())
    seq = eng.state_manager.get(5)
    assert not seq.draftable and seq.draft is None
    eng.extend(5, int(got[-1].argmax()))
    eng.admit(6, PROMPTS[1])
    stream = {5: toks + [int(got[-1].argmax())], 6: []}
    for _ in range(12):
        for u, burst in eng.step_bursts().items():
            stream[u].extend(burst)
            eng.extend(u, burst[-1])
            assert u == 6 or len(burst) == 1
    assert stream[5] == plain_streams[0][:len(stream[5])]
    assert stream[6] == plain_streams[1][:len(stream[6])]
    assert eng.drafts_verified > 0      # sequence 6 drafted beside it


# -- experts, rotary ------------------------------------------------------
def test_the_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """An expert layer of the trunk and the module's, each as sixteen
    shares of one expert: their routed parts and the shared expert ONCE
    add up to the reference's layer with all sixteen held, the scaling
    factor 2.5 in both."""
    base = get_model_config(PRESET)
    whole = base.replace(mla=dataclasses.replace(base.mla,
                                                 experts_held=(0, 16)))
    params = tf_model.init_params(whole, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(9), (21, 64))
    assert whole.mla.routed_scaling_factor == 2.5
    ref_cfg = reference_config(whole)
    for name, stack, i, ln2 in (
            ("trunk", params["layers"]["moe"], 1,
             params["layers"]["ln2"]["scale"][3]),
            ("module", params["mtp"]["moe"], 0,
             params["mtp"]["ffn_norm"]["scale"])):
        stack = dict(stack, bias=0.3 * jax.random.normal(
            jax.random.PRNGKey(2), stack["bias"].shape))
        want = np.asarray(reference._experts(ref_cfg, jax.devices()[0])(
            x[None], ln2, stack, i))[0]
        h = latent._rms(x, ln2, whole)
        shared = tf_model._mlp_block(h, latent._at(stack["shared"], i), whole)
        routed = 0
        for e in range(16):
            cfg = whole.replace(mla=dataclasses.replace(
                whole.mla, experts_held=(e, 1)))
            share = dict(stack, **{n: stack[n][:, e:e + 1]
                                   for n in ("wg", "wi", "wo")})
            routed = routed + latent._feed_forward(
                x, ln2, share, i, True, cfg) - x - shared
        np.testing.assert_allclose(x + routed + shared, want, atol=2e-5,
                                   err_msg=name)
        # the factor is in it: without, the routed part is 2.5 times less
        plain = whole.replace(mla=dataclasses.replace(
            whole.mla, routed_scaling_factor=1.0))
        once = latent._feed_forward(x, ln2, stack, i, True, plain) - x - shared
        np.testing.assert_allclose(2.5 * once, routed, atol=2e-5)


def test_interleaved_and_half_split_rotary_differ_and_each_is_its_reference():
    tokens = ids(30, seed=8)
    rows = {}
    for interleaved in (True, False):
        base = get_model_config(PRESET)
        model = base.replace(mla=dataclasses.replace(
            base.mla, rope_interleaved=interleaved))
        eng = engine(model)
        got, toks = put_stream(eng, 1, tokens, decode=2)
        want = np.asarray(reference.logits(
            eng.params, np.asarray([tokens + toks]), reference_config(model),
            jax.devices()[0], last=3))[0]
        np.testing.assert_allclose(got, want,
                                   atol=1e-4 * np.abs(want).max())
        rows[interleaved] = got[0]
    assert np.abs(rows[True] - rows[False]).max() \
        > 1e-2 * np.abs(rows[True]).max()
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 8))
    pos = jnp.arange(5)
    pairs = reference.rope_pairs(x[None], pos[None], 1e4)[0]
    # the program leaves the pairs' members in two halves
    np.testing.assert_allclose(
        latent._rope(x, pos, 1e4, True),
        jnp.concatenate([pairs[..., 0::2], pairs[..., 1::2]], -1), atol=1e-6)


# -- spans, presets, refusals ---------------------------------------------
def test_presets_hold_the_published_sizes():
    model = get_model_config("glm-5")
    m = model.mla
    assert (model.num_layers, model.hidden_size, model.vocab_size,
            model.intermediate_size) == (78, 6144, 154880, 12288)
    kinds = m.kinds(78)
    assert all(full for full, _ in kinds) and not m.has_window(78)
    assert [e for _, e in kinds] == [False] * 3 + [True] * 75
    assert (m.full.row_dim, m.full.qk_head_dim, m.full.v_head_dim,
            m.index_topk, m.index_heads) == (576, 256, 256, 2048, 32)
    assert (m.gate, m.lora_rescale, m.rope_interleaved, m.mtp_layers,
            m.routed_scaling_factor, m.window) == (False, False, True, 1,
                                                   2.5, None)
    assert m.cache_layers(78) == 79
    share = get_model_config("glm-5-ep16", num_layers=5, first_k_dense=1)
    assert share.mla.experts_held == (0, 16) and share.vocab_size == 19360
    assert [e for _, e in share.mla.kinds(5)] == [False] + [True] * 4
    assert share.mla.cache_layers(5) == 6
    shapes = jax.eval_shape(lambda k: tf_model.init_params(share, k),
                            jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 4_802_856_704, n
    assert (model.latent_row, model.window_row, model.mtp_layers,
            model.first_k_dense, model.routed_scaling_factor) == (
                576, 0, 1, 3, 2.5)
    assert get_model_config("dots3-note-tiny").mtp_layers == 0


def test_self_draft_needs_a_module_and_no_state():
    with pytest.raises(ValueError, match="multi-token-prediction"):
        InferenceEngineV2(get_model_config("dots3-note-tiny"), {
            "dtype": "float32", "self_draft": True,
            "memory_config": {"num_blocks": 32, "block_size": 4},
            "max_context": 64})
    with pytest.raises(ValueError, match="multi-token-prediction"):
        InferenceEngineV2(get_model_config("llama-tiny"),
                          {"self_draft": True})


def test_what_a_latent_model_without_rings_is_no_longer_refused(
        plain_streams):
    """``verify_step`` and ``rewind`` (an EXTERNAL draft) serve a latent
    model whose layers are all full, held experts included: accepted
    prefixes and the bonus token are plain greedy decoding's."""
    eng = engine()
    uid, prompt, want = 0, PROMPTS[0], plain_streams[0]
    eng.admit(uid, prompt)
    out = {}
    while uid not in out:
        out = eng.step(temperature=0.0)
    eng.extend(uid, out[uid])
    got = [out[uid]]
    assert got == want[:1]
    # two right and one wrong: the two and the target's own third
    props = [want[1], want[2], (want[3] + 1) % 512, want[4]]
    accepted = eng.verify_step({uid: props})[uid]
    assert accepted == want[1:4]
    got += accepted
    # a caller takes the last token back, and it comes again
    seq = eng.state_manager.get(uid)
    eng.rewind(uid, seq.tokens[:-1], seq.num_cached - 1)
    assert eng.step(temperature=0.0)[uid] == want[3]
    eng.extend(uid, want[3])
    assert eng.verify_step({uid: []})[uid] == [want[4]]
    _, args = eng.audit_step_args("verify")
    assert len(args) == 4


@pytest.mark.parametrize("self_draft", [False, True],
                         ids=["plain", "self_draft"])
def test_a_prefix_cache_serves_a_latent_model_without_rings(self_draft):
    """Pages of latent rows and index keys are adopted as any pages are
    (no ring beside them to be missing): requests that share a prompt's
    head skip its prefill and say what a server without the cache says.
    On a self-drafting engine a sequence that adopted pages drafts no
    more (its donor's steps may have run without the module)."""
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    head = ids(24, seed=6)
    prompts = [head + ids(n, seed=7 + n) for n in (5, 9, 3)]
    want = engine().generate(prompts, max_new_tokens=10)
    eng = engine(self_draft=self_draft)
    srv = InferenceServer(eng, {"prefix_cache": {"enabled": True}})
    srv.start()
    try:
        got = [list(srv.submit(p, SamplingParams(max_new_tokens=10)))
               for p in prompts]
    finally:
        srv.stop(drain=False, timeout=60)
    assert got == want
    assert srv.metrics.prefix_hits == 2
    assert srv.metrics.snapshot()["prefill_tokens_saved"] == 2 * 24


def test_what_is_still_refused_and_why():
    eng = engine(self_draft=True)
    eng.admit(1, PROMPTS[0])
    eng.step(temperature=0.0)
    eng.step(temperature=0.0)
    for call in (lambda: eng.verify_step({1: [3]}),
                 lambda: eng.rewind(1, PROMPTS[0], 4)):
        with pytest.raises(ValueError, match="drafts for itself"):
            call()
    for call in (lambda: eng.export_kv_chain(1),
                 lambda: eng.import_kv_chain({"geom": eng.kv_geometry(),
                                              "tokens": []})):
        with pytest.raises(NotImplementedError, match="latent row"):
            call()
    # the training forward and a caller that makes per-head pages
    from deepspeed_tpu.inference.kv_generate import KVCachedGenerator

    model = get_model_config(PRESET)
    with pytest.raises(NotImplementedError, match="latent .MLA. attention"):
        tf_model.forward(eng.params, jnp.zeros((1, 8), jnp.int32), model)
    with pytest.raises(ValueError, match="per-head pages"):
        KVCachedGenerator(model, block_size=8).generate(
            eng.params, np.ones((1, 4), np.int32), 2)
    # rings and a mixer: still by name, each for its own reason
    ringed = InferenceEngineV2(get_model_config("dots3-note-tiny"), {
        "dtype": "float32",
        "memory_config": {"num_blocks": 32, "block_size": 4},
        "max_context": 64})
    with pytest.raises(RecurrentStateUnsupported,
                       match="sliding-window latent layers"):
        ringed.verify_step({1: [3]})
    mixer = get_model_config("falcon-h1-tiny")
    with pytest.raises(NotImplementedError, match="recurrent"):
        jax.eval_shape(lambda: v2_model.ragged_forward_verify(
            None, None, None, *([jnp.zeros((16,), jnp.int32)] * 4),
            jnp.zeros((5, 4), jnp.int32), jnp.zeros((5,), jnp.int32),
            jnp.zeros((5,), jnp.int32), cfg=mixer, block_size=4))
    with pytest.raises(NotImplementedError, match="capacity-routed"):
        jax.eval_shape(lambda: v2_model.ragged_forward_verify(
            None, None, None, *([jnp.zeros((16,), jnp.int32)] * 4),
            jnp.zeros((5, 4), jnp.int32), jnp.zeros((5,), jnp.int32),
            jnp.zeros((5,), jnp.int32),
            cfg=get_model_config("mixtral-tiny"), block_size=4))
