"""Inference v2 (FastGen analog): allocator, scheduler, paged decode parity.

Ref test model: tests/unit/inference/v2/ (ragged ops, kv cache, engine).
The key correctness oracle: continuous-batching paged-KV generation must
produce EXACTLY the same greedy tokens as the v1 engine's full-recompute
generation with the same weights.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (BlockedAllocator, DSStateManager,
                                        SplitFuseScheduler, build_engine)
from deepspeed_tpu.models import get_model_config


def test_blocked_allocator():
    a = BlockedAllocator(8)
    assert a.free_blocks == 7  # block 0 reserved
    got = a.allocate(3)
    assert len(set(got)) == 3 and 0 not in got
    with pytest.raises(RuntimeError):
        a.allocate(5)
    a.free(got)
    assert a.free_blocks == 7
    with pytest.raises(ValueError):
        a.free([0])


def test_blocked_allocator_rejects_double_free_and_bad_handles():
    """A double-freed page would be handed to two sequences and silently
    cross-write their KV — free() must reject it, plus handles that were
    never valid, without mutating the free list."""
    a = BlockedAllocator(8)
    got = a.allocate(3)
    a.free(got[:1])
    with pytest.raises(ValueError, match="double free"):
        a.free(got[:1])                    # already returned
    with pytest.raises(ValueError, match="not allocated"):
        a.free([5] if 5 not in got else [6])  # never handed out
    with pytest.raises(ValueError, match="out of range"):
        a.free([99])
    with pytest.raises(ValueError, match="out of range"):
        a.free([-1])
    with pytest.raises(ValueError, match="duplicate"):
        a.free([got[1], got[1]])
    # failed frees must not have leaked: the two live handles still free
    a.free(got[1:])
    assert a.free_blocks == 7
    from deepspeed_tpu.inference.v2 import KVCacheExhausted

    with pytest.raises(KVCacheExhausted):  # typed for the serving layer
        a.allocate(8)


def test_state_manager_slots_and_pages():
    mgr = DSStateManager(max_seqs=2, num_blocks=8, block_size=4,
                         max_blocks_per_seq=4)
    s1 = mgr.open(10, [1, 2, 3, 4, 5])
    mgr.ensure_capacity(s1, 5)
    assert len(s1.blocks) == 2
    s2 = mgr.open(11, [7])
    with pytest.raises(RuntimeError):
        mgr.open(12, [9])  # no slots
    mgr.flush(10)
    assert 10 not in mgr and mgr.allocator.free_blocks == 7
    mgr.open(12, [9])  # slot reusable
    mgr.flush(11), mgr.flush(12)


def test_splitfuse_schedule_splits_prompts():
    mgr = DSStateManager(max_seqs=4, num_blocks=64, block_size=4,
                         max_blocks_per_seq=16)
    sched = SplitFuseScheduler(mgr, token_budget=8)
    mgr.open(1, list(range(20)))  # long prompt
    sched.add(1)
    s = sched.next_schedule()
    assert [(x.uid, n) for x, n in s] == [(1, 8)]
    # simulate the engine caching those tokens
    mgr.get(1).num_cached = 8
    s = sched.next_schedule()
    assert [(x.uid, n) for x, n in s] == [(1, 8)]
    mgr.get(1).num_cached = 16
    s = sched.next_schedule()
    assert [(x.uid, n) for x, n in s] == [(1, 4)]  # final chunk → sampled
    mgr.get(1).num_cached = 20


def test_splitfuse_decode_priority():
    mgr = DSStateManager(max_seqs=4, num_blocks=64, block_size=4,
                         max_blocks_per_seq=16)
    sched = SplitFuseScheduler(mgr, token_budget=8)
    mgr.open(1, [1, 2, 3])
    sched.add(1)
    sched.next_schedule()
    mgr.get(1).num_cached = 3       # prompt done → decode set
    mgr.get(1).tokens.append(42)    # sampled token pending
    mgr.open(2, list(range(30)))
    sched.add(2)
    s = sched.next_schedule()
    # decode seq first (1 token), then prompt chunk fills the rest
    assert (s[0][0].uid, s[0][1]) == (1, 1)
    assert (s[1][0].uid, s[1][1]) == (2, 7)


@pytest.mark.parametrize("model_name", ["llama-tiny", "gpt2-tiny"])
def test_paged_generation_matches_v1(model_name):
    """Greedy continuous-batching output == full-recompute output."""
    from deepspeed_tpu.inference.engine import InferenceEngine

    model = get_model_config(model_name, num_layers=2)
    v1 = InferenceEngine(model, {"dtype": "float32"}, seed=3)
    v2 = build_engine(model, {"dtype": "float32",
                              "state_manager": {"max_tracked_sequences": 4,
                                                "max_ragged_batch_size": 16},
                              "memory_config": {"num_blocks": 64, "block_size": 4},
                              "max_context": 128},
                      model_params=v1.params, seed=3)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, model.vocab_size, size=n).tolist()
               for n in (5, 11, 3)]
    new = 8
    got = v2.generate(prompts, max_new_tokens=new)
    for prompt, out in zip(prompts, got):
        ref = v1.generate(np.asarray(prompt)[None], max_new_tokens=new)
        assert out == ref[0, len(prompt):].tolist()


def test_paged_generation_moe():
    model = get_model_config("mixtral-tiny", num_layers=2)
    v2 = build_engine(model, {"dtype": "float32",
                              "memory_config": {"num_blocks": 64, "block_size": 4},
                              "max_context": 64},
                      seed=0)
    out = v2.generate([[1, 2, 3], [4, 5]], max_new_tokens=4)
    assert all(len(o) == 4 for o in out)
    assert all(0 <= t < model.vocab_size for o in out for t in o)


def test_kv_pages_freed_after_generate():
    model = get_model_config("llama-tiny", num_layers=1)
    v2 = build_engine(model, {"dtype": "float32",
                              "memory_config": {"num_blocks": 32, "block_size": 4},
                              "max_context": 64}, seed=0)
    before = v2.free_blocks
    v2.generate([[1, 2, 3, 4, 5]], max_new_tokens=3)
    assert v2.free_blocks == before


def test_continuous_batching_oversubscribed():
    """More prompts than slots: engine drains in waves, all finish."""
    model = get_model_config("llama-tiny", num_layers=1)
    v2 = build_engine(model, {"dtype": "float32",
                              "state_manager": {"max_tracked_sequences": 2,
                                                "max_ragged_batch_size": 16},
                              "memory_config": {"num_blocks": 16, "block_size": 4},
                              "max_context": 32}, seed=0)
    prompts = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10]]
    out = v2.generate(prompts, max_new_tokens=3)
    assert all(len(o) == 3 for o in out)


def test_generate_raises_on_impossible_prompt():
    model = get_model_config("llama-tiny", num_layers=1)
    v2 = build_engine(model, {"dtype": "float32",
                              "memory_config": {"num_blocks": 4, "block_size": 4},
                              "max_context": 16}, seed=0)
    with pytest.raises(RuntimeError):
        v2.generate([list(range(1, 30))], max_new_tokens=8)


def test_admission_reserves_active_seq_future_blocks():
    """Tight KV cache: active sequences' future pages are reserved, so the
    second prompt waits instead of overcommitting and crashing mid-stream."""
    model = get_model_config("llama-tiny", num_layers=1)
    v2 = build_engine(model, {"dtype": "float32",
                              "state_manager": {"max_tracked_sequences": 4,
                                                "max_ragged_batch_size": 16},
                              "memory_config": {"num_blocks": 8, "block_size": 4},
                              "max_context": 32}, seed=0)
    out = v2.generate([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], max_new_tokens=12)
    assert all(len(o) == 12 for o in out)
    assert v2.free_blocks == v2.cfg.num_blocks - 1


def test_admission_enforces_per_seq_block_cap():
    """Prompt fits the cache but exceeds max_blocks_per_seq → friendly error
    at admission, not a mid-generate crash."""
    model = get_model_config("llama-tiny", num_layers=1)
    v2 = build_engine(model, {"dtype": "float32",
                              "memory_config": {"num_blocks": 64, "block_size": 4},
                              "max_context": 16}, seed=0)
    with pytest.raises(RuntimeError, match="per sequence"):
        v2.generate([[1, 2, 3], list(range(1, 25))], max_new_tokens=4)


def test_put_validates_batch_before_mutating():
    model = get_model_config("llama-tiny", num_layers=1)
    v2 = build_engine(model, {"dtype": "float32",
                              "memory_config": {"num_blocks": 32, "block_size": 4},
                              "max_context": 32}, seed=0)
    with pytest.raises(ValueError):
        v2.put([1, 1], [[5, 6], [7, 8]])     # duplicate uid in one batch
    assert 1 not in v2.state_manager          # nothing half-admitted
    with pytest.raises(ValueError):
        v2.put([2, 3], [[5, 6]])              # mismatched lengths
    assert 2 not in v2.state_manager


def test_build_ragged_batch_checks_budget_first():
    from deepspeed_tpu.inference.v2.ragged import build_ragged_batch

    mgr = DSStateManager(max_seqs=2, num_blocks=16, block_size=4,
                         max_blocks_per_seq=4)
    seq = mgr.open(1, list(range(10)))
    with pytest.raises(RuntimeError, match="budget"):
        build_ragged_batch([(seq, 10)], mgr, token_budget=8)
    assert seq.num_cached == 0  # state untouched


@pytest.mark.parametrize("floor,tokens,want", [
    (1, 5, 2), (1, 20, 8), (4, 5, 4), (4, 20, 8), (4, 60, 16), (16, 5, 16)])
def test_context_bucket_is_the_floor_times_a_power_of_two(floor, tokens, want):
    """``state_manager.min_context_blocks``: the narrowest block table a
    step is cut to; 1 (the default) leaves the buckets as they were."""
    from deepspeed_tpu.inference.v2.ragged import build_ragged_batch

    mgr = DSStateManager(max_seqs=2, num_blocks=32, block_size=4,
                         max_blocks_per_seq=16, min_blocks_bucket=floor)
    seq = mgr.open(0, list(range(tokens)))
    rb = build_ragged_batch([(seq, tokens)], mgr, token_budget=64)
    assert rb.index.blocks == want
    with pytest.raises(ValueError, match="min_context_blocks"):
        DSStateManager(max_seqs=2, num_blocks=32, block_size=4,
                       max_blocks_per_seq=16, min_blocks_bucket=32)


def test_soak_staggered_eos_and_sampling_allocator_clean():
    """Soak: three generate() waves with eos cut-offs, varying lengths and
    nucleus sampling — the allocator must return to fully-free after every
    wave (no leaked pages/slots across waves; ref flush/retire paths)."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("gpt2-tiny")
    eng = InferenceEngineV2(model, {"dtype": "float32",
                                    "memory_config": {"num_blocks": 64,
                                                      "block_size": 16},
                                    "max_context": 128})
    free0 = eng.free_blocks
    rng = np.random.default_rng(21)
    for wave, (n, temp, tp) in enumerate([(6, 0.0, 1.0), (4, 0.9, 0.8),
                                          (8, 0.7, 1.0)]):
        prompts = [list(map(int, rng.integers(
            1, model.vocab_size, size=(int(rng.integers(2, 24)),))))
            for _ in range(n)]
        outs = eng.generate(prompts, max_new_tokens=int(rng.integers(3, 12)),
                            temperature=temp, top_p=tp,
                            eos_token_id=7)
        assert len(outs) == n
        for o in outs:
            assert len(o) >= 1
            if 7 in o:  # eos respected: nothing after it
                assert o[o.index(7):] == [7]
        assert eng.free_blocks == free0, (wave, eng.free_blocks, free0)
        assert eng.state_manager.n_active == 0
    from deepspeed_tpu.parallel import topology

    topology._GLOBAL_TOPOLOGY = None


def test_compile_time_guard_for_small_block_sizes():
    """ceil(max_context/block_size) > 256 is a multi-minute TPU compile
    (observed >880 s at 512 blocks/seq on v5e, r04) — the engine refuses
    it up front unless allow_slow_compile opts in; >128 warns only."""
    import pytest

    from deepspeed_tpu.inference.v2.engine_v2 import (
        RaggedInferenceEngineConfig)

    with pytest.raises(ValueError, match="blocks per sequence"):
        RaggedInferenceEngineConfig({
            "max_context": 32768, "memory_config": {"block_size": 64}})
    cfg = RaggedInferenceEngineConfig({
        "max_context": 32768, "memory_config": {"block_size": 64},
        "allow_slow_compile": True})
    assert cfg.block_size == 64
    # the default operating point (2048 / 16 = 128) stays silent
    cfg = RaggedInferenceEngineConfig({})
    assert -(-cfg.max_context // cfg.block_size) == 128


def test_int8_kv_cache_generation():
    """memory_config.kv_dtype=int8: the cache stores int8 payload + fp32
    per-row scales (half the KV bytes), generation runs the quantize-on-
    append path, and greedy outputs match the bf16 cache closely."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.parallel import topology

    model = get_model_config("llama-tiny")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, model.vocab_size, size=(12,)).tolist()
               for _ in range(3)]
    outs = {}
    for kind in ("bf16", "int8"):
        eng = InferenceEngineV2(
            model, {"memory_config": {"kv_dtype": kind}}, seed=11)
        if kind == "int8":
            assert eng.cache_k["q"].dtype == jnp.int8
            assert eng.cache_k["s"].dtype == jnp.float32
            # payload bytes halve vs the bf16 cache; scales add 4/(2d)
            assert eng.cache_k["q"].nbytes * 2 == bf16_nbytes
            d = eng.cache_k["q"].shape[-1]
            assert eng.cache_k["s"].nbytes * d == eng.cache_k["q"].nbytes * 4
        else:
            assert eng.cache_k.dtype == jnp.bfloat16
            bf16_nbytes = eng.cache_k.nbytes
        outs[kind] = eng.generate(prompts, max_new_tokens=8)
        topology._GLOBAL_TOPOLOGY = None
    # greedy decode over a random tiny model: quantization noise may flip
    # an occasional argmax, but the sequences must agree on most tokens
    agree = np.mean([np.mean(np.asarray(a[:4]) == np.asarray(b[:4]))
                     for a, b in zip(outs["bf16"], outs["int8"])])
    assert agree >= 0.5, (agree, outs)


# -- the pools ride the layer loop's carry: same bits as sliced and restacked -
_CARRY_CASES = {
    # preset, overrides, int8 cache, attention pinned
    "llama": ("llama-tiny", {}, False, None),
    "llama_bf16": ("llama-tiny", {"dtype": "bfloat16"}, False, None),
    "llama_int8_cache": ("llama-tiny", {}, True, None),
    "mistral_window": ("mistral-tiny", {}, False, None),
    "qwen2_qkv_bias": ("qwen2-tiny", {}, False, None),
    "gptneo_alt_window": ("gptneo-tiny", {}, False, None),
    "mixtral_moe": ("mixtral-tiny", {}, False, None),
    "bloom_alibi": ("bloom-tiny", {}, False, None),
    "falcon_h1_mixer": ("falcon-h1-tiny", {}, False, None),
    # the kernels (interpreted), which read the pool by a layer index
    "head128_kernel": ("mistral-tiny", {"hidden_size": 256, "num_heads": 2,
                                        "num_kv_heads": 1}, False,
                       "paged_pallas"),
    "head128_kernel_int8_cache": ("llama-tiny", {
        "hidden_size": 256, "num_heads": 2, "num_kv_heads": 1}, True,
        "paged_pallas"),
}


def _carry_case(name):
    """A model (a name of ``_CARRY_CASES`` or such a tuple), its weights,
    two pools filled with noise and one step: a
    20-row chunk of sequence 0 at positions 10..29 (it straddles two
    pages of 16), a decode row of sequence 1 at position 33, three
    padding rows that write to the garbage page 0."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model as m2
    from deepspeed_tpu.models import transformer as tf_model

    preset, over, int8, attention = (
        _CARRY_CASES[name] if isinstance(name, str) else name)
    over = dict(over)
    if "dtype" in over:
        over["dtype"] = getattr(jnp, over["dtype"])
    if attention:
        over["v2_modules"] = (("attention", attention),)
    cfg = get_model_config(preset, **over)
    params = jax.jit(lambda k: tf_model.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    bs, n_pages = 16, 12
    shape = (cfg.num_layers, cfg.kv_heads, n_pages * bs, cfg.dim_per_head)

    def pool(seed):
        rng = np.random.default_rng(seed)
        if int8:
            return {"q": jnp.asarray(rng.integers(-127, 128, size=shape),
                                     jnp.int8),
                    "s": jnp.asarray(rng.uniform(0.01, 0.02, size=shape[:3]),
                                     jnp.float32)}
        return jnp.asarray(rng.normal(size=shape), cfg.dtype)

    tables = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [0, 0, 0]], np.int32)
    slot = np.array([0] * 20 + [1] + [3] * 3, np.int32)
    pos = np.array(list(range(10, 30)) + [33] + [0] * 3, np.int32)
    dest = np.where(slot < 3, tables[slot, pos // bs] * bs + pos % bs,
                    0).astype(np.int32)
    ids = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=slot.shape).astype(np.int32)
    step = [jnp.asarray(a) for a in (
        ids, slot, pos, dest, tables, np.array([30, 34, 0, 0], np.int32),
        np.array([19, 20, 0, 0], np.int32))]
    state = None
    if cfg.ssm is not None:
        state = jax.tree.map(
            lambda a: jnp.asarray(np.random.default_rng(5).normal(
                size=a.shape), a.dtype), m2.new_ssm_state(cfg, 3))
    return cfg, params, pool(2), pool(3), step, state, bs


def _sliced_and_restacked(params, cache_k, cache_v, token_ids, token_slot,
                          token_pos, token_dest, block_tables, ctx_lens,
                          state, *, cfg, block_size, state_slot=None):
    """The trunk as it was before the pools rode the carry: every layer's
    pages are sliced out of the pool into a value of their own, updated
    there and stacked into a NEW pool.  Returns (x, cache_k', cache_v',
    state') as ``_ragged_trunk`` does."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model as m2
    from deepspeed_tpu.models.transformer import _norm

    ssm_meta = m2._ssm_meta(cfg, state, token_slot if state_slot is None
                            else state_slot, token_pos)
    x = m2._embed_rows(params, token_ids, token_pos, cfg)
    meta = m2._step_meta(token_slot, token_pos, token_dest, block_tables,
                         ctx_lens, block_size, cache_k, cfg)
    every = max(1, cfg.moe_layer_freq)
    # layer pairs where the window alternates, as the old loop scanned them
    period = 2 if cfg.alt_window else 1
    group = lambda tree: jax.tree.map(
        lambda a: a.reshape((cfg.num_layers // period, period)
                            + a.shape[1:]), tree)
    ungroup = lambda tree: jax.tree.map(
        lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), tree)

    def body(carry, scanned):
        h, ssm = carry
        lp, k_group, v_group, first, conv_group = scanned
        k_out, v_out, conv_out = [], [], []
        for j in range(period):
            member = lambda tree, j=j: jax.tree.map(lambda a: a[j], tree)
            lcfg = cfg
            if cfg.alt_window and j % 2 == 0:
                lcfg = cfg.replace(sliding_window=None)
            is_moe = cfg.is_moe and (
                True if every == 1 else (first + j) % every == every - 1)
            st = ({"ssm": ssm, "conv": conv_group[j], "layer": first + j}
                  if cfg.ssm else None)
            # the layer's pages, alone: a pool of one layer, its layer 0
            h, k1, v1, st = m2._ragged_layer(
                h, member(lp), jax.tree.map(lambda a: a[None],
                                            member(k_group)),
                jax.tree.map(lambda a: a[None], member(v_group)), 0, meta,
                lcfg, layer_is_moe=is_moe, state=st, ssm_meta=ssm_meta)
            k_out.append(k1)
            v_out.append(v1)
            if cfg.ssm:
                ssm = st["ssm"]
                conv_out.append(st["conv"])
        stack = lambda pools: jax.tree.map(
            lambda *a: jnp.concatenate(a, axis=0), *pools)
        return (h, ssm), (stack(k_out), stack(v_out),
                          jnp.stack(conv_out) if cfg.ssm else None)

    (x, ssm), (cache_k, cache_v, conv) = jax.lax.scan(
        body, (x, state["ssm"] if cfg.ssm else None),
        (group(params["layers"]), group(cache_k), group(cache_v),
         jnp.arange(0, cfg.num_layers, period),
         group(state["conv"]) if cfg.ssm else None))
    if cfg.ssm:
        state = {"ssm": ssm, "conv": ungroup(conv)}
    return (_norm(x, params["final_norm"], cfg), ungroup(cache_k),
            ungroup(cache_v), state)


def _assert_same_bits(got, want):
    import jax

    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g.astype("float32")),
                                      np.asarray(w.astype("float32")))


@pytest.fixture
def interpreted_kernels():
    import importlib

    pm = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")
    old, pm.INTERPRET = pm.INTERPRET, True
    yield
    pm.INTERPRET = old


@pytest.mark.parametrize("name", list(_CARRY_CASES))
def test_carried_pools_same_bits_as_sliced_and_restacked(
        name, interpreted_kernels):
    """``ragged_forward``'s logits, pools and recurrent state against the
    formulation it replaced, bit for bit: a chunk whose rows straddle two
    pages, a decode row, padding rows into the garbage page."""
    import functools

    import jax

    from deepspeed_tpu.inference.v2 import model as m2

    cfg, params, ck, cv, step, state, bs = _carry_case(name)
    ids, slot, pos, dest, tables, ctx, logits_idx = step
    got = jax.jit(functools.partial(m2.ragged_forward, cfg=cfg,
                                    block_size=bs))(
        params, ck, cv, *step, state)

    @jax.jit
    def want_fn(params, ck, cv, state):
        x, ck, cv, state = _sliced_and_restacked(
            params, ck, cv, ids, slot, pos, dest, tables, ctx, state,
            cfg=cfg, block_size=bs)
        logits = m2._lm_head(x[logits_idx], params, cfg)
        if cfg.ssm:
            logits = logits * cfg.ssm.lm_head_multiplier
        out = (logits.astype("float32"), ck, cv)
        return out + (state,) if cfg.ssm else out

    want = want_fn(params, ck, cv, state)
    _assert_same_bits(got, want)
    # the step wrote: the pools are not what came in, outside page 0 too
    k_in, k_out = jax.tree.leaves(ck)[0], jax.tree.leaves(got[1])[0]
    assert not np.array_equal(np.asarray(k_in[:, :, bs:].astype("float32")),
                              np.asarray(k_out[:, :, bs:].astype("float32")))


def test_pools_with_no_append_kernel_keep_the_row_scatter(
        interpreted_kernels, monkeypatch):
    """Pools of which not a page fits the append kernel's VMEM (here: a
    budget of nothing) are appended to by the row scatter, chosen from the
    shape while the program is traced: the same logits and, from page 1
    on, the same pools."""
    import functools

    import jax

    from deepspeed_tpu.inference.v2 import model as m2
    from deepspeed_tpu.ops.pallas import kv_append as ka

    cfg, params, ck, cv, step, state, bs = _carry_case("head128_kernel")
    run = lambda: jax.jit(functools.partial(
        m2.ragged_forward, cfg=cfg, block_size=bs))(params, ck, cv, *step,
                                                    state)
    calls = []
    kernel = m2.kv_append
    monkeypatch.setattr(m2, "kv_append", lambda *a, **kw: (
        calls.append(1), kernel(*a, **kw))[1])
    want = run()
    assert calls
    del calls[:]
    monkeypatch.setattr(ka, "_VMEM_BUDGET", 0)
    got = run()
    assert not calls
    _assert_same_bits((got[0], got[1][:, :, bs:], got[2][:, :, bs:]),
                      (want[0], want[1][:, :, bs:], want[2][:, :, bs:]))


@pytest.mark.parametrize("name", ["llama", "mistral_window",
                                  "head128_kernel"])
def test_verify_step_same_bits_as_sliced_and_restacked(name,
                                                       interpreted_kernels):
    """``ragged_forward_verify`` runs the same trunk: every row's argmax
    and the pools, bit for bit."""
    import functools

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model as m2

    cfg, params, ck, cv, step, _, bs = _carry_case(name)
    got = jax.jit(functools.partial(m2.ragged_forward_verify, cfg=cfg,
                                    block_size=bs))(params, ck, cv, *step)

    @jax.jit
    def want_fn(params, ck, cv):
        x, ck, cv, _ = _sliced_and_restacked(
            params, ck, cv, *step[:-1], None, cfg=cfg, block_size=bs)
        logits = m2._lm_head(x, params, cfg).astype(jnp.float32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), ck, cv

    _assert_same_bits(got, want_fn(params, ck, cv))


@pytest.mark.parametrize("name", ["llama", "gptneo_alt_window",
                                  "falcon_h1_mixer", "head128_kernel"])
def test_decode_loop_same_bits_as_sliced_and_restacked(name,
                                                       interpreted_kernels):
    """``ragged_decode_loop`` hands its carried pools to the layer loop's
    carry: four fused steps (one slot inactive, its row the garbage
    page's) against four single steps of the old formulation."""
    import functools

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model as m2

    cfg, params, ck, cv, step, state, bs = _carry_case(name)
    tables = step[4][:3]
    tokens0 = step[0][:3]
    ctx0 = jnp.asarray([30, 15, 0], jnp.int32)
    active = jnp.asarray([True, True, False])
    key, n_steps = jax.random.PRNGKey(0), 4
    got = jax.jit(functools.partial(
        m2.ragged_decode_loop, cfg=cfg, block_size=bs, n_steps=n_steps,
        greedy=True))(params, ck, cv, tokens0, ctx0, active, tables, key,
                      jnp.float32(1.0), state=state)

    slots = jnp.arange(3, dtype=jnp.int32)
    # an inactive row's recurrent state is the garbage slot's
    state_slot = None if state is None else jnp.where(
        active, slots, state["ssm"].shape[1] - 1)

    @jax.jit
    def one_step(params, ck, cv, state, tokens, ctx):
        dest = jnp.where(active, tables[slots, ctx // bs] * bs + ctx % bs, 0)
        ctx_after = ctx + active.astype(jnp.int32)
        x, ck, cv, state = _sliced_and_restacked(
            params, ck, cv, tokens, slots, ctx, dest, tables, ctx_after,
            state, cfg=cfg, block_size=bs, state_slot=state_slot)
        logits = m2._lm_head(x, params, cfg).astype(jnp.float32)
        if cfg.ssm:
            logits = logits * cfg.ssm.lm_head_multiplier
        nxt = jnp.where(active, jnp.argmax(logits, -1).astype(jnp.int32), 0)
        return nxt, ctx_after, ck, cv, state

    tokens, ctx, st = tokens0, ctx0, state
    sampled = []
    for _ in range(n_steps):
        tokens, ctx, ck, cv, st = one_step(params, ck, cv, st, tokens, ctx)
        sampled.append(tokens)
    want = (jnp.stack(sampled), ctx, ck, cv) + ((st,) if cfg.ssm else ())
    _assert_same_bits(got, want)


# -- q, k, v: products of their own behind a barrier (PERF.md, PR 36) --------
def _seed_attn_biases(params, seed=7):
    """The projections' biases initialise to zeros: give them values."""
    import jax

    attn = params["layers"]["attn"]
    for i, name in enumerate(n for n in ("bq", "bk", "bv", "bo")
                             if n in attn):
        attn[name] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(seed + i), attn[name].shape, attn[name].dtype)
    return params


@pytest.fixture
def tapped_qkv(monkeypatch):
    """What ``_ragged_layer`` hands on while a program is traced: q to
    ``_paged_attention``; k and v to ``kv_append``, both in one call, on
    the kernels' path, and k then v to ``_kv_append`` on the XLA path.
    ``tapped_qkv(fn)`` calls ``fn(name, array)`` on each, inside the
    traced program."""
    import itertools

    from deepspeed_tpu.inference.v2 import model as m2

    attend, append, append_pages = (m2._paged_attention, m2._kv_append,
                                    m2.kv_append)
    appended = itertools.cycle("kv")

    def install(fn):
        def tap_attend(q, *a, **kw):
            fn("q", q)
            return attend(q, *a, **kw)

        def tap_append(pool, x, *a, **kw):
            fn(next(appended), x)
            return append(pool, x, *a, **kw)

        def tap_append_pages(cache_k, cache_v, k, v, *a, **kw):
            fn("k", k)
            fn("v", v)
            return append_pages(cache_k, cache_v, k, v, *a, **kw)

        monkeypatch.setattr(m2, "_paged_attention", tap_attend)
        monkeypatch.setattr(m2, "_kv_append", tap_append)
        monkeypatch.setattr(m2, "kv_append", tap_append_pages)

    return install


# preset, programs: a mixer's recurrent slots are refused by verify, and its
# decode loop is held above (test_decode_loop_same_bits_...)
_QKV_MODELS = [("qwen2-tiny", "step"), ("qwen2-tiny", "verify"),
               ("qwen2-tiny", "decode_loop"), ("falcon-h1-tiny", "step"),
               ("mistral-tiny", "step")]
# the kernels' path (interpreted): heads of 128, the page-granular append
_QKV_KERNELS = {"mistral-tiny": ({"hidden_size": 256, "num_heads": 2,
                                  "num_kv_heads": 1}, "paged_pallas")}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("preset,program", _QKV_MODELS)
def test_qkv_behind_the_barrier_are_the_plain_products(preset, program, dtype,
                                                       tapped_qkv,
                                                       interpreted_kernels):
    """One block, biases seeded: the q, k, v that each ragged program's
    block attends with and appends are ``rope(norm(x) @ w + b)``, computed
    here outside any program (GQA + rope + bias; Falcon-H1's multipliers
    beside its mixer).  In bfloat16 the product's float32 accumulator goes
    through bias, key multiplier and rope and is rounded once (held to one
    step of bfloat16, and to the bit in all but a hundredth of the
    elements: a second rounding in between moves far more of them):
    the TPU's compiler had folded the roundings between away, and a
    barrier after a rounding would put them back; the key multiplier
    itself is the model's dtype's, as it was when it scaled a bfloat16 k
    (PERF.md, PR 36)."""
    import functools

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model as m2
    from deepspeed_tpu.models.transformer import _norm

    over, attention = _QKV_KERNELS.get(preset, ({}, None))
    cfg, params, ck, cv, step, state, bs = _carry_case(
        (preset, {"num_layers": 1, "dtype": dtype, **over}, False, attention))
    params = _seed_attn_biases(params)
    seen = {}
    tapped_qkv(lambda name, x: jax.debug.callback(
        lambda a: seen.__setitem__(name, np.asarray(a)), x))
    if program == "decode_loop":
        ids, pos = step[0][:3], jnp.asarray([30, 15, 0], jnp.int32)
        out = jax.jit(functools.partial(
            m2.ragged_decode_loop, cfg=cfg, block_size=bs, n_steps=1,
            greedy=True))(params, ck, cv, ids, pos,
                          jnp.asarray([True, True, False]), step[4][:3],
                          jax.random.PRNGKey(0), jnp.float32(1.0))
    else:
        ids, pos = step[0], step[2]
        fn, extra = ((m2.ragged_forward, (state,)) if program == "step"
                     else (m2.ragged_forward_verify, ()))
        out = jax.jit(functools.partial(fn, cfg=cfg, block_size=bs))(
            params, ck, cv, *step, *extra)
    jax.block_until_ready(out)
    jax.effects_barrier()

    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = _norm(m2._embed_rows(params, ids, pos, cfg), lp["ln1"], cfg)
    if cfg.ssm:
        h = h * cfg.ssm.attention_in_multiplier
    t, d = ids.shape[0], cfg.dim_per_head
    plain = {}
    for n in "qkv":
        y = jnp.matmul(h, lp["attn"]["w" + n].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)
        if "b" + n in lp["attn"]:
            assert float(jnp.abs(lp["attn"]["b" + n]).max()) > 0.1
            y = y + lp["attn"]["b" + n].astype(cfg.dtype)
        plain[n] = y.reshape(t, -1, d)
    if cfg.ssm:
        plain["k"] = plain["k"] * jnp.asarray(
            cfg.ssm.key_multiplier, cfg.dtype).astype(jnp.float32)
    for n in "qk":
        plain[n] = m2._rope_tok(plain[n], pos, cfg)
    assert sorted(seen) == ["k", "q", "v"]
    for n in "qkv":
        want = np.asarray(plain[n].astype(cfg.dtype).astype(jnp.float32))
        got = seen[n].astype(np.float32)
        if dtype == "bfloat16":
            # one step of bfloat16 (8 significant bits) at the element's
            # magnitude: where the program's compiler and this test's
            # contract the float32 sum in another order, an element on a
            # rounding edge lands one step off, by machine (CHANGES.md,
            # PR 39: one of 3,072 in falcon-h1-tiny's step)
            step = 2.0 ** (np.floor(np.log2(np.maximum(
                np.abs(want), np.float32(2.0 ** -126)))) - 7)
            off = np.abs(got - want)
            assert np.all(off <= step), (n, float((off / step).max()))
            assert np.mean(off > 0) < 0.01, (n, float(np.mean(off > 0)))
        else:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


_PREFILL_MODELS = {
    "gqa_rope_bias": ("qwen2-tiny", {}),
    "bias_no_rope": ("opt-tiny", {}),
    "head64_paged_xla": ("gpt2-tiny", {"num_heads": 2}),
}


def _prefill_logits(eng, uid, prompt):
    """The next-token logits of ``prompt``, prefilled in the engine's
    chunks; the sequence is flushed."""
    out = eng.put([uid], [prompt])
    while uid not in out:
        out = eng.put([], [])
    eng.flush(uid)
    return np.asarray(out[uid])


def _tiny_engine(model, params=None, **over):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    return InferenceEngineV2(model, {
        "dtype": "float32", "max_context": 64,
        "state_manager": {"max_tracked_sequences": 2,
                          "max_ragged_batch_size": 8},
        "memory_config": {"num_blocks": 32, "block_size": 4}, **over},
        model_params=params, seed=3)


@pytest.mark.parametrize("name", list(_PREFILL_MODELS))
def test_chunked_prefill_logits_match_forward(name):
    """A 21-token prompt through three 8-row steps against the training
    model's ``forward`` in float32, the projections' biases seeded."""
    import jax

    from deepspeed_tpu.models import transformer as tf_model

    preset, over = _PREFILL_MODELS[name]
    model = get_model_config(preset, **over)
    if name == "head64_paged_xla":
        assert model.dim_per_head == 64
    eng = _tiny_engine(model)
    assert eng.attention_impl == "paged_xla"
    eng.params = _seed_attn_biases(eng.params)
    prompt = [int(x) for x in np.random.default_rng(2).integers(
        0, model.vocab_size, size=21)]
    got = _prefill_logits(eng, 1, prompt)
    want = np.asarray(jax.jit(lambda p, ids: tf_model.forward(
        p, ids, eng.model_config))(eng.params, np.asarray([prompt])))[0, -1]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_weight_swap_reaches_the_next_step():
    """The step reads ``engine.params`` as they are when it is called:
    there is no prepared form of the projections to go stale."""
    import jax

    from deepspeed_tpu.models import transformer as tf_model

    model = get_model_config("qwen2-tiny")
    eng = _tiny_engine(model)
    prompt = list(range(3, 16))
    before = _prefill_logits(eng, 1, prompt)
    swapped = _seed_attn_biases(jax.jit(
        lambda k: tf_model.init_params(eng.model_config, k))(
        jax.random.PRNGKey(11)))
    eng.params = jax.device_put(swapped,
                                eng.rules.tree_shardings(swapped))
    after = _prefill_logits(eng, 2, prompt)
    want = np.asarray(jax.jit(lambda p, ids: tf_model.forward(
        p, ids, eng.model_config))(swapped, np.asarray([prompt])))[0, -1]
    np.testing.assert_allclose(after, want, rtol=2e-4, atol=2e-4)
    assert np.abs(after - before).max() > 0.1


def test_qkv_stay_column_parallel_under_tp2(tapped_qkv):
    """``tp_size=2``: behind the barrier q, k and v are still split over
    the tensor axis by KV-head group (q's heads 0-1 with KV head 0), and
    the logits are the unsharded engine's."""
    import jax

    model = get_model_config("qwen2-tiny")
    prompt = list(range(3, 16))
    base = _tiny_engine(model)
    base.params = _seed_attn_biases(base.params)
    want = _prefill_logits(base, 1, prompt)

    shards = {}
    tapped_qkv(lambda name, x: jax.debug.inspect_array_sharding(
        x, callback=lambda s: shards.__setitem__(
            name, (x.shape, s.shard_shape(x.shape)))))
    eng = _tiny_engine(model, params=jax.device_get(base.params),
                       tensor_parallel={"tp_size": 2})
    got = _prefill_logits(eng, 1, prompt)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert sorted(shards) == ["k", "q", "v"]
    for name, heads in (("q", model.num_heads), ("k", model.kv_heads),
                        ("v", model.kv_heads)):
        (t, nh, d), shard = shards[name]
        assert nh == heads and shard == (t, heads // 2, d), (name, shards)
