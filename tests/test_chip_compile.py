"""The main paths' Pallas kernels, compiled at real widths by the TPU
v5e's own compiler — for a chip that is described, not attached.

Interpret-mode tests cannot see what Mosaic refuses (a slice off the
128-lane tiling, a block shape the lowering does not take); these compiles
can, at no chip time.  Nothing runs, so they say nothing about results or
speed — ``chip_smoke.py`` does that on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under
pytest-xdist every worker imports every test file.
"""

import functools
import importlib
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

flash_mha_mod = importlib.import_module("deepspeed_tpu.ops.pallas.flash_mha")
from deepspeed_tpu.ops.pallas import (fused_optimizer, gather_matmul,  # noqa: E402
                                      paged_attention, quantize, ssd_ragged)

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one — keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` → an abstract array on one described v5e."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    """Compile for the described chip; the text must hold a Mosaic kernel.
    conftest.py asks for "highest" matmul precision (CPU numerics), which
    no program on the chip runs under — compile as the chip would."""
    with jax.default_matmul_precision("default"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# [B, Hq, Hkv, S, D, window] — flash_mha takes [B, H, S, D]
FLASH_SHAPES = {
    "gpt2-350m": (8, 16, 16, 1024, 64, None),
    "opt-1.3b": (2, 32, 32, 2048, 64, None),     # a chip's share of zero3
    "mistral-7b-2k": (2, 32, 8, 2048, 128, 4096),
    "mistral-7b-8k": (1, 32, 8, 8192, 128, 4096),
}


@pytest.mark.parametrize("name", list(FLASH_SHAPES))
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_mha(chip, name, grad):
    b, hq, hkv, s, d, window = FLASH_SHAPES[name]
    assert flash_mha_mod.supports(s, d)

    def fwd(q, k, v):
        return flash_mha_mod.flash_mha(q, k, v, True, None, window)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(F32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(fn, chip((b, hq, s, d), BF16), chip((b, hkv, s, d), BF16),
             chip((b, hkv, s, d), BF16))


# the serve shape: 32 decoding tokens, mistral-7b heads, 128 pages of 16
_T, _NH, _NKV, _BS, _NB, _ROWS = 32, 32, 8, 16, 128, 1024 * 16


def _paged_args(chip, d, kv_dtype):
    return (chip((_T, _NH, d), BF16), chip((_NKV, _ROWS, d), kv_dtype),
            chip((_NKV, _ROWS, d), kv_dtype), chip((_T, _NB), I32),
            chip((_T,), I32), chip((_T,), I32))


def test_paged_decode_serve_shape(chip):
    assert paged_attention.supports(_BS, 128)
    fn = functools.partial(paged_attention.paged_decode_attention,
                           block_size=_BS, sm_scale=128 ** -0.5, window=4096)
    _compile(fn, *_paged_args(chip, 128, BF16))


@pytest.mark.parametrize("t", [256, 16])
def test_paged_qblock_serve_step(chip, t):
    """The ragged step as the serving cells run it: block tables of 64
    sequences and a padding row, 256 pages of 16 a sequence, 8 KV heads
    of 4 query heads, head 128; a full 256-token step and the smallest
    token bucket (below the query block)."""
    def fn(q, k, v, tables, pos, clen, slot):
        return paged_attention.paged_decode_attention(
            q, k, v, tables, pos, clen, block_size=_BS,
            sm_scale=128 ** -0.5, window=4096, token_slot=slot)

    pool = chip((_NKV, 2048 * _BS, 128), BF16)
    _compile(fn, chip((t, _NH, 128), BF16), pool, pool, chip((65, 256), I32),
             chip((t,), I32), chip((t,), I32), chip((t,), I32))


@pytest.mark.parametrize("t", [64, 256], ids=["decode_only", "with_chunk"])
def test_ssd_ragged_published_shapes(chip, t):
    """The state-space scan at Falcon-H1-34B's widths (32 heads of 128,
    state 256, 2 groups, chunks of 128) over six layers' slots of 64
    sequences and the pad's: a decode-only step (a bucket of 64 rows,
    padded to one chunk, every row a run of one) and a full 256-row step
    that carries a prompt's state from its first chunk to its second.
    The state must come back in place."""
    def fn(x, dt, a, b, c, state, slot, pos):
        return ssd_ragged.ssd_ragged_pallas(x, dt, a, b, c, state, slot, pos,
                                            layer=jnp.int32(3), chunk=128)

    with jax.default_matmul_precision("default"):
        text = jax.jit(fn, donate_argnums=5).lower(
            chip((t, 32, 128), BF16), chip((t, 32), F32), chip((32,), F32),
            chip((t, 2, 256), BF16), chip((t, 2, 256), BF16),
            chip((6, 65, 32, 128, 256), F32), chip((t,), I32),
            chip((t,), I32)).compile().as_text()
    assert "tpu_custom_call" in text
    call = next(ln for ln in text.splitlines()
                if "custom-call" in ln and "ssd_ragged" in ln)
    assert "output_to_operand_aliasing" in call
    assert "f32[6,65,32,128,256]" in call


# -- the WHOLE ragged step: the pools ride the layer loop's carry ------------
def _abstract(chip, tree):
    return jax.tree.map(lambda a: chip(a.shape, a.dtype), tree)


def _compile_step(chip, cfg, pool, nb, t, state=None):
    """``ragged_step_sampled`` for one described v5e as the engine jits
    it (pools and a mixer's state donated, the index arrays one packed
    buffer): 64 sequences and the padding row, ``nb`` pages of 16 a
    sequence, ``t`` rows.  Abstract weights."""
    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.inference.v2.ragged import PackedIndex
    from deepspeed_tpu.models import transformer as tf_model

    params = _abstract(chip, jax.eval_shape(
        lambda k: tf_model.init_params(cfg, k), jax.random.PRNGKey(0)))
    index = PackedIndex(chip((PackedIndex.size(t, 65, nb),), I32), t, 65, nb)
    fn = functools.partial(v2_model.ragged_step_sampled, cfg=cfg,
                           block_size=_BS, greedy=True)
    # (the tokens the step before sampled, by slot: the fed-back operand)
    args = (params, pool, pool, index, chip((65,), I32),
            chip((2,), jnp.uint32), chip((), F32))
    kw = {} if state is None else {"state": state}
    with jax.default_matmul_precision("default"):
        return jax.jit(fn, donate_argnums=(1, 2),
                       donate_argnames=("state",) if kw else None).lower(
            *args, **kw).compile()


def _aliased_outputs(text):
    """{output index: parameter number} of the compiled module."""
    import re

    head = text.split("input_output_alias={", 1)[1].split(
        ", entry_computation_layout", 1)[0]
    return {int(o): int(p) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", head)}


def _assert_pools_in_place(compiled, pool_dims, n_aliased, temp_limit):
    """The step returns every donated buffer as the buffer it came in,
    keeps no temporary the size of a layer's pages, copies and slices no
    pool-shaped or layer-of-pool-shaped array, and hands the kernel the
    whole pool."""
    import re

    text = compiled.as_text()
    assert len(_aliased_outputs(text)) == n_aliased, _aliased_outputs(text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_limit, mem
    assert mem.alias_size_in_bytes >= 2 * 2 * math.prod(pool_dims)
    whole = "bf16[" + ",".join(map(str, pool_dims)) + "]"
    one_layer = "bf16[" + ",".join(map(str, pool_dims[1:])) + "]"
    moved = []
    for line in text.splitlines():
        # ``%name = shape{layout} op(operands...)``; a fusion is named for
        # what it holds (``copy_dynamic-update-slice_fusion.4``)
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+?)[{ ]\S* ?([\w-]+)\(", line)
        if m and m.group(2) in (whole, one_layer) and re.search(
                r"copy|dynamic-slice|dynamic-update-slice",
                m.group(1) if m.group(3) == "fusion" else m.group(3)):
            moved.append(line.strip()[:160])
    assert not moved, moved
    call = next(ln for ln in text.splitlines()
                if "custom-call(" in ln and "paged_qblock" in ln)
    layouts = call.split("operand_layout_constraints={", 1)[1]
    assert layouts.count(whole) == 2, call[:400]


# the serving cells' models: preset, modules, pool [L, nkv, P, d], pages a
# sequence.  ``mistral-7b-l16``: 16 layers at the published widths, two
# pools of 1 GiB, 65 x 256 block tables.  ``falcon-h1-34b-l6``: a Mamba-2
# mixer in every block at Falcon-H1-34B's widths, six layers, the pools
# beside the recurrent slots.
_SERVED = {
    "mistral-7b-l16": ("mistral-7b", (("attention", "paged_pallas"),),
                       (16, 8, 2048 * _BS, 128), 256),
    "falcon-h1-34b-l6": ("falcon-h1-34b", (("attention", "paged_pallas"),
                                           ("ssm", "ssd_pallas")),
                         (6, 4, 4352 * _BS, 128), 64),
}


@pytest.fixture(scope="module")
def serving_step(chip):
    """``serving_step(model, rows)`` → (cfg, pool dims, compiled): the
    serving step of a model of ``_SERVED``, compiled once for every test
    that reads it."""
    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.models import get_model_config

    @functools.lru_cache(maxsize=None)
    def step(model, t):
        preset, modules, dims, nb = _SERVED[model]
        cfg = get_model_config(preset, num_layers=dims[0], param_dtype=BF16,
                               dtype=BF16, v2_modules=modules)
        state = None if cfg.ssm is None else _abstract(chip, jax.eval_shape(
            lambda: v2_model.new_ssm_state(cfg, 64)))
        return cfg, dims, _compile_step(chip, cfg, chip(dims, BF16), nb, t,
                                        state=state)

    return step


@pytest.mark.parametrize("t", [256, 16])
def test_ragged_step_carries_the_pools_mistral_7b_l16(serving_step, t):
    """A full 256-row step and the smallest bucket.  As the scan's xs/ys
    the pools cost 2.5 GiB of temporaries and three passes over both
    pools a step (PERF.md, PR 30)."""
    _, dims, compiled = serving_step("mistral-7b-l16", t)
    # activations only: far below one layer's pages (64 MiB)
    _assert_pools_in_place(compiled, dims, n_aliased=2,
                           temp_limit=16 * 2 ** 20)


def test_ragged_step_carries_the_pools_beside_a_mixer(serving_step):
    """The pools ride the carry beside the recurrent slots, and all four
    donated arrays come back in place."""
    _, dims, compiled = serving_step("falcon-h1-34b-l6", 256)
    # a layer's pages are 68 MiB here; the mixer's activations are most
    # of what is left
    _assert_pools_in_place(compiled, dims, n_aliased=4,
                           temp_limit=64 * 2 ** 20)
    call = next(ln for ln in compiled.as_text().splitlines()
                if "tpu_custom_call" in ln and "%ssd_ragged" in ln)
    assert "output_to_operand_aliasing" in call


def _computations(text):
    """{name: (header, [instruction lines])} of a compiled module."""
    import re

    out, lines = {}, None
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", ln)
        if m:
            lines = []
            out[m.group(1)] = (ln, lines)
        elif lines is not None and ln.startswith("  "):
            lines.append(ln)
    return out


@pytest.mark.parametrize("model,t", [("mistral-7b-l16", 256),
                                     ("mistral-7b-l16", 16),
                                     ("falcon-h1-34b-l6", 256)])
def test_ragged_step_streams_the_qkv_weights(serving_step, model, t):
    """The layer loop's q, k and v products read the stacked weights from
    HBM inside the fusion that multiplies, as ``wo``'s and the MLP's do.
    With the heads' reshape folded into the product the compiler wants
    the weight ``[out][in]``: it slices the layer's matrix out of the
    stack, transposes the copy and only then multiplies (PERF.md, PR 36:
    124 us a layer at mistral-7b's widths against 78 us streamed)."""
    import collections
    import re

    cfg, _, compiled = serving_step(model, t)
    comps = _computations(compiled.as_text())
    body = next(lines for _, lines in comps.values() if any(
        "custom-call(" in ln and "paged_qblock" in ln for ln in lines))
    n, h = cfg.num_layers, cfg.hidden_size
    q_out, kv_out = (cfg.num_heads * cfg.dim_per_head,
                     cfg.kv_heads * cfg.dim_per_head)
    one_layer = {f"bf16[1,{h},{q_out}]", f"bf16[1,{h},{kv_out}]"}
    moved = [ln.strip()[:160] for ln in body if (m := re.match(
        r"\s*(?:ROOT )?%\S+ = (\S+?)[{ ]", ln)) and m.group(1) in one_layer]
    assert not moved, moved
    streamed = collections.Counter()
    for ln in body:
        m = re.search(r" fusion\(.*calls=%([^\s,]+)", ln)
        if m and any(" convolution(" in x for x in comps[m.group(1)][1]):
            streamed.update(re.findall(r": (bf16\[[\d,]+\])",
                                       comps[m.group(1)][0]))
    want = collections.Counter(
        [f"bf16[{n},{h},{q_out}]", f"bf16[{n},{h},{kv_out}]",
         f"bf16[{n},{h},{kv_out}]", f"bf16[{n},{q_out},{h}]"])
    assert not want - streamed, (want, streamed)


@pytest.mark.parametrize("model,t", [("mistral-7b-l16", 256),
                                     ("mistral-7b-l16", 16),
                                     ("falcon-h1-34b-l6", 256)])
def test_ragged_step_appends_both_pools_in_one_kernel(serving_step, model, t):
    """The layer loop puts the step's K and V rows down through ONE
    ``kv_append`` call, both pools aliased in place, and scatters into no
    pool (two row scatters of 2,048 updates each took 16 % of the chip's
    time in ``longdoc_closed``: PERF.md, PR 41).  One call and not one a
    pool: half the kernels to lower and to launch."""
    import re

    _, dims, compiled = serving_step(model, t)
    text = compiled.as_text()
    comps = _computations(text)
    body = next(lines for _, lines in comps.values() if any(
        "custom-call(" in ln and "paged_qblock" in ln for ln in lines))
    calls = [ln for ln in body if "custom-call(" in ln and "kv_append" in ln]
    assert len(calls) == 1, calls
    whole = "bf16[" + ",".join(map(str, dims)) + "]"
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}\)", calls[0].split(
        "output_to_operand_aliasing={", 1)[1].split(", frontend_attr", 1)[0])
    assert len(aliased) == 2, calls[0][:600]
    result = calls[0].split(" custom-call(", 1)[0]
    assert result.count(whole) == 2, result
    # nowhere in the program: a scatter, or a fusion that holds one, with
    # a pool among its operands or as its result
    scatters = [ln.strip()[:200] for _, lines in comps.values()
                for ln in lines if " scatter(" in ln and whole in ln]
    assert not scatters, scatters
    assert sum("kv_append" in ln and "custom-call(" in ln
               for ln in text.splitlines()) == 1


@pytest.mark.parametrize("t,nkv,d,bs,dtype", [
    (2048, 8, 128, 16, BF16), (256, 4, 256, 16, BF16), (64, 8, 128, 16, F32),
    (256, 32, 128, 16, BF16), (256, 8, 128, 64, BF16),
    (256, 8, 128, 128, BF16), (256, 32, 128, 128, BF16),
    (16, 32, 128, 128, BF16), (256, 8, 128, 8, BF16)],
    ids=["eight_row_blocks", "head_256", "float32_pool", "kv_heads_32",
         "page_64", "page_128", "kv_heads_32_page_128",
         "kv_heads_32_page_128_decode", "page_8"])
def test_kv_append_other_shapes(chip, t, nkv, d, bs, dtype):
    """What ``paged_attention.supports`` admits and no serving cell runs:
    a token budget of several row blocks, a head of two lane tiles, a
    float32 pool, as many kv heads as heads (gpt2, a llama without GQA),
    pages of 64 and 128 rows (what the engine advises for long contexts)
    and of 8: ``fit`` sizes the rows and pages a program holds to its
    VMEM from these, and the compiler takes each."""
    from deepspeed_tpu.ops.pallas import kv_append as ka

    def fn(ck, cv, k, v, dest, layer):
        return ka.kv_append(ck, cv, k, v, ka.step_pages(ck, dest, bs), layer,
                            bs)

    pool = chip((4, nkv, 64 * bs, d), dtype)
    rows = chip((t, nkv, d), BF16)
    text = jax.jit(fn, donate_argnums=(0, 1)).lower(
        pool, pool, rows, rows, chip((t,), I32), chip((), I32)
    ).compile().as_text()
    call = next(ln for ln in text.splitlines()
                if "custom-call(" in ln and "kv_append" in ln)
    assert "output_to_operand_aliasing={{0}: (8, {}), {1}: (9, {})}" in call


# MiMo-V2-Flash's layers at the published widths: 64 query heads, keys 192
# wide (kept in 256 lanes, ``paged_attention.row_width``) and values 128,
# 4 KV heads in a full layer and 8 in a window-128 layer whose softmax
# starts from a learned sink a head; pages of 128 rows, 161 a sequence
_MIMO_KINDS = {"full": (2, 4, None, False), "window_sink": (5, 8, 128, True)}


@pytest.mark.parametrize("t", [1024, 16, 32, 64],
                         ids=["chunk", "decode_bucket", "bucket_32",
                              "bucket_64"])
@pytest.mark.parametrize("kind", list(_MIMO_KINDS))
def test_paged_qblock_keys_192_values_128_heads_by_kind(chip, kind, t):
    """Every bucket's tile is wider than ``narrow_rows``, so each
    compiles the narrow path of a one-row run too: a 16-row window of the
    query tile and of the softmax state at a run-time 16-row boundary."""
    layers, nkv, window, sink = _MIMO_KINDS[kind]
    assert paged_attention.narrow_rows(
        64 // nkv, min(t, paged_attention.QUERY_BLOCK) * 64 // nkv) == 16
    assert paged_attention.supports(128, 192, 128)
    dk = paged_attention.row_width(192)

    def fn(q, k, v, tables, pos, clen, slot, layer, *sinks):
        return paged_attention.paged_decode_attention(
            q, k, v, tables, pos, clen, block_size=128,
            sm_scale=192 ** -0.5, window=window, token_slot=slot,
            layer=layer, sink=sinks[0] if sinks else None)

    out = jax.eval_shape(
        fn, chip((t, 64, dk), BF16), chip((layers, nkv, 512 * 128, dk), BF16),
        chip((layers, nkv, 512 * 128, 128), BF16), chip((65, 161), I32),
        chip((t,), I32), chip((t,), I32), chip((t,), I32), chip((), I32),
        *([chip((64,), F32)] if sink else []))
    assert out.shape == (t, 64, 128)
    _compile(fn, chip((t, 64, dk), BF16),
             chip((layers, nkv, 512 * 128, dk), BF16),
             chip((layers, nkv, 512 * 128, 128), BF16), chip((65, 161), I32),
             chip((t,), I32), chip((t,), I32), chip((t,), I32),
             chip((), I32), *([chip((64,), F32)] if sink else []))


@pytest.mark.parametrize("t", [1024, 16], ids=["chunk", "decode_bucket"])
@pytest.mark.parametrize("kind", list(_MIMO_KINDS))
def test_kv_append_rows_of_two_widths(chip, kind, t):
    """K rows of 256 lanes (192 dims) and V rows of 128 go to their pages
    in ONE call, both pools aliased."""
    from deepspeed_tpu.ops.pallas import kv_append as ka

    layers, nkv, _, _ = _MIMO_KINDS[kind]

    def fn(ck, cv, k, v, dest, layer):
        return ka.kv_append(ck, cv, k, v, ka.step_pages(ck, dest, 128, cv),
                            layer, 128)

    text = jax.jit(fn, donate_argnums=(0, 1)).lower(
        chip((layers, nkv, 64 * 128, 256), BF16),
        chip((layers, nkv, 64 * 128, 128), BF16), chip((t, nkv, 256), BF16),
        chip((t, nkv, 128), BF16), chip((t,), I32), chip((), I32)
    ).compile().as_text()
    call = next(ln for ln in text.splitlines()
                if "custom-call(" in ln and "kv_append" in ln)
    assert "output_to_operand_aliasing={{0}: (8, {}), {1}: (9, {})}" in call


@pytest.mark.parametrize("t", [1024, 16], ids=["full_step", "decode_bucket"])
def test_kinds_of_two_shapes_step_keeps_all_four_pools_in_place(chip, t):
    """The serving step of ``mimo-v2-flash-ep16-l7`` (seven layers at the
    published widths, the configuration's pools and 161 pages a
    sequence): the full layers' pools ``[2, 4, P, 256]`` / ``[2, 4, P,
    128]`` and the window layers' ``[5, 8, P, 256]`` / ``[5, 8, P, 128]``
    are donated and come back as the buffers they came in, no pool or
    layer of a pool is copied or sliced whole, the five window layers are
    ONE scan, and the step makes three call sites of each kernel (layer
    0, the scan's body, layer 6)."""
    import re

    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.inference.v2.ragged import PackedIndex
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models import transformer as tf_model

    cfg = get_model_config("mimo-v2-flash-ep16", param_dtype=BF16,
                           v2_modules=(("attention", "paged_pallas"),))
    assert v2_model.layer_segments(cfg.mixed.kinds(7)) \
        == [(0, 1, 1), (1, 1, 5), (6, 1, 1)]
    params = _abstract(chip, jax.eval_shape(
        lambda k: tf_model.init_params(cfg, k), jax.random.PRNGKey(0)))
    ck, cv, state = v2_model.new_window_pools(
        cfg, 2560 * 128, 256 * 128, zeros=lambda s, dtype: chip(s, dtype),
        dtype=BF16)
    index = PackedIndex(
        chip((PackedIndex.size(t, 65, 161, window=True),), I32), t, 65, 161,
        window=True)
    fn = functools.partial(v2_model.ragged_step_sampled, cfg=cfg,
                           block_size=128, greedy=True)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn, donate_argnums=(1, 2),
                           donate_argnames=("state",)).lower(
            params, ck, cv, index, chip((65,), I32), chip((2,), jnp.uint32),
            chip((), F32), state=state).compile()
    text = compiled.as_text()
    assert len(_aliased_outputs(text)) == 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 28, mem
    pools = (ck, cv, state["k"], state["v"])
    assert mem.alias_size_in_bytes >= sum(2 * math.prod(a.shape)
                                          for a in pools)
    shapes = {"bf16[" + ",".join(map(str, a.shape[i:])) + "]"
              for a in pools for i in (0, 1)}
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+?)[{ ]\S* ?([\w-]+)\(", line)
        if m and m.group(2) in shapes and re.search(
                r"copy|dynamic-slice|dynamic-update-slice",
                m.group(1) if m.group(3) == "fusion" else m.group(3)):
            moved.append(line.strip()[:160])
    assert not moved, moved
    for kernel in ("paged_qblock", "kv_append"):
        assert sum(kernel in ln and "custom-call(" in ln
                   for ln in text.splitlines()) == 3


def test_latent_index_scores_at_dots3_widths(chip):
    """The indexer's score kernel: 64 heads of 128 over pages of 128
    rows, a full 1024-row step at a 32k context bucket (an output block
    of 32 x 32768 float32 a program)."""
    from deepspeed_tpu.ops.pallas import latent_index

    fn = functools.partial(latent_index.index_scores, block_size=128)
    text = _compile(fn, chip((1024, 64, 128), BF16), chip((1024, 64), F32),
                    chip((2, 2048 * 128, 128), BF16), chip((), I32),
                    chip((33, 256), I32), chip((1024,), I32),
                    chip((1024,), I32), chip((1024,), I32))
    assert "latent_index_scores" in text


_DOTS3_EXPERTS = ["bf16[32,5120,1536]", "bf16[32,1536,5120]"]
_GLM5_EXPERTS = ["bf16[16,6144,2048]", "bf16[16,2048,6144]"]


def _assert_latent_buffers_stay(text, buffers, experts, by_layer=False):
    """No operation of the compiled ``text`` copies or transposes a value
    the shape of one of ``buffers`` (with ``by_layer``: of one layer of
    it), and none copies or slices a stack of ``experts`` matrices."""
    import re

    held = ["bf16[%s]" % ",".join(map(str, a.shape)) for a in buffers]
    if by_layer:
        held += ["bf16[%s]" % ",".join(map(str, a.shape[1:]))
                 for a in buffers]
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+?)[{ ]\S* ?([\w-]+)\(", line)
        if not m:
            continue
        op = m.group(1) if m.group(3) == "fusion" else m.group(3)
        if m.group(2) in held and re.search(r"copy|transpose", op):
            moved.append(line.strip()[:160])
        if m.group(2) in experts and re.search(r"copy|slice", op):
            moved.append(line.strip()[:160])
    assert not moved, moved


# the latent cells' models at five layers and the published widths: preset,
# its overrides, pool rows, the self-drafting step?
_LATENT = {
    "glm-5-ep16-l5": ("glm-5-ep16", {"num_layers": 5, "first_k_dense": 1},
                      1024 * 128, True),
    "dots3-note-ep8-l5": ("dots3-note-prev-ep8", {"num_layers": 5},
                          2048 * 128, False),
}


@pytest.fixture(scope="module")
def latent_step(chip):
    """``latent_step(model, rows, pages)`` -> (cfg, params, (cache_k,
    cache_v, state), compiled): the step of a model of ``_LATENT`` as the
    engine jits it (GLM-5: the self-drafting step), 32 sequences and the
    padding row, pages of 128, the indexer's kernel named; compiled once
    for every test that reads it."""
    from deepspeed_tpu.inference.v2 import latent
    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.inference.v2.ragged import PackedIndex
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models import transformer as tf_model

    @functools.lru_cache(maxsize=None)
    def step(model, t, nb):
        preset, over, rows, drafting = _LATENT[model]
        cfg = get_model_config(
            preset, param_dtype=BF16, dtype=BF16,
            v2_modules=(("indexer", "indexer_pallas"),), **over)
        params = _abstract(chip, jax.eval_shape(
            lambda k: tf_model.init_params(cfg, k), jax.random.PRNGKey(0)))
        ck, cv, state = jax.eval_shape(
            lambda: latent.new_cache(cfg, rows, 32, t))
        ck, cv = _abstract(chip, (ck, cv))
        index = PackedIndex(
            chip((PackedIndex.size(t, 33, nb, drafting),), I32), t, 33, nb,
            drafting)
        with jax.default_matmul_precision("default"):
            if drafting:
                assert state is None
                fn = functools.partial(v2_model.ragged_draft_step, cfg=cfg,
                                       block_size=128)
                compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
                    params, ck, cv, index, chip((4, 33), I32)).compile()
            else:
                state = _abstract(chip, state)
                fn = functools.partial(v2_model.ragged_step_sampled, cfg=cfg,
                                       block_size=128, greedy=True)
                compiled = jax.jit(fn, donate_argnums=(1, 2),
                                   donate_argnames=("state",)).lower(
                    params, ck, cv, index, chip((33,), I32),
                    chip((2,), jnp.uint32), chip((), F32),
                    state=state).compile()
        return cfg, params, (ck, cv, state), compiled

    return step


def test_latent_step_keeps_its_pools_and_rings_in_place(latent_step):
    """The serving step of ``dots3-note-ep8-l5`` (five layers at the
    published widths, a full 1024-row step at an 8k context bucket): the
    pages of latent rows ``bf16[2,P,640]`` and of index keys
    ``bf16[2,P,128]`` and the window layers' rings ``bf16[3,33,1664,1152]``
    are donated, come back in place, and are never copied whole or by
    layer (declared 576 and 1088 wide, the rows' own widths, the TPU's
    compiler turns the pools rows-minor for the gather and copies them
    there and back in every layer: PERF.md, PR 34); no layer's stack of
    expert matrices is sliced out as a value of its own either."""
    rows = 2048 * 128
    _, _, (ck, cv, state), compiled = latent_step("dots3-note-ep8-l5", 1024,
                                                  64)
    assert ck.shape == (2, rows, 640) and cv.shape == (2, rows, 128)
    assert state["win"].shape == (3, 33, 1664, 1152)
    text = compiled.as_text()
    assert len(_aliased_outputs(text)) == 3
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2 ** 30, mem
    assert "latent_index_scores" in text
    _assert_latent_buffers_stay(text, (ck, cv, state["win"]), _DOTS3_EXPERTS)


@pytest.mark.parametrize("t", [512, 16], ids=["full_step", "decode_only"])
def test_one_mixer_a_layer_step_keeps_every_buffer_in_place(chip, t):
    """The serving step of ``nemotron-3-nano-ep2-l9`` (nine layers at the
    published widths: four scans of 64 heads of 64 in 8 groups at state
    128, one attention layer of 16 query heads to each of 2 KV heads, four
    layers of 64 held two-matrix experts), a full 512-row step and the
    smallest bucket, 256 sequences and the padding row: the recurrent
    slots ``f32[4,257,64,64,128]``, the convolution's tails, one row a
    sequence and layer, and the attention layer's pools are donated and
    come back in place; neither they nor the stacked expert weights are
    copied, transposed or sliced whole in any layer (the experts' up
    matrices stored ``[F, H]``: as ``[H, 1856]`` the stack came in lanes
    along ``H`` and was copied whole, 2.5 GB a step; the tails as ``[4,
    257, 3, 6144]`` came in with the layers on the sublanes); the step makes
    the Pallas calls the engine counts, four scans, one append, one
    read."""
    import re

    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.inference.v2.ragged import PackedIndex
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models import transformer as tf_model

    cfg = get_model_config(
        "nemotron-3-nano-30b-a3b-ep2", num_layers=9, param_dtype=BF16,
        dtype=BF16, v2_modules=(("attention", "paged_pallas"),
                                ("ssm", "ssd_pallas")))
    bs, nb, slots = 32, 128, 257
    params = _abstract(chip, jax.eval_shape(
        lambda k: tf_model.init_params(cfg, k), jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(params)) == 3_166_244_352
    pool = chip((1, 2, 32769 * bs, 128), BF16)
    state = _abstract(chip, jax.eval_shape(
        lambda: v2_model.new_ssm_state(cfg, slots - 1)))
    assert state["ssm"].shape == (4, 257, 64, 64, 128)
    assert state["conv"].shape == (4 * 264, 3 * 6144)
    index = PackedIndex(chip((PackedIndex.size(t, slots, nb),), I32), t,
                        slots, nb)
    fn = functools.partial(v2_model.ragged_step_sampled, cfg=cfg,
                           block_size=bs, greedy=True)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn, donate_argnums=(1, 2),
                           donate_argnames=("state",)).lower(
            params, pool, pool, index, chip((slots,), I32),
            chip((2,), jnp.uint32), chip((), F32), state=state).compile()
    text = compiled.as_text()
    # both pools, the slots and the tails come back as they came in
    assert len(_aliased_outputs(text)) == 4, _aliased_outputs(text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 256 * 2 ** 20, mem
    held = {"f32[4,257,64,64,128]", "bf16[1056,18432]",
            "bf16[1,2,1048608,128]", "bf16[2,1048608,128]"}
    experts = {"bf16[4,64,1856,2688]", "bf16[64,1856,2688]",
               "bf16[4,2688,3712]", "bf16[4,3712,2688]"}
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(?(\S+?)[{ ]\S* ?([\w-]+)\(", line)
        if not m:
            continue
        # (a ``copy-start`` / ``copy-done`` pair is the compiler moving a
        # buffer to its faster memory and back as it lies, not a relayout)
        op = m.group(1) if m.group(3) == "fusion" else m.group(3) + "("
        if m.group(2) in held | experts and re.search(
                r"copy\(|copy_|transpose", op):
            moved.append(line.strip()[:160])
        if m.group(2) in experts and re.search(r"slice", op):
            moved.append(line.strip()[:160])
    assert not moved, moved
    names = [re.search(r"%(\w+?)[.\d]* = ", ln).group(1)
             for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert sorted(names) == ["kv_append", "paged_qblock"] + ["ssd_ragged"] * 3
    scan = next(ln for ln in text.splitlines()
                if "tpu_custom_call" in ln and "%ssd_ragged" in ln)
    assert "output_to_operand_aliasing" in scan
    assert "f32[4,257,64,64,128]" in scan


@pytest.mark.parametrize("t,nb", [(1024, 64), (32, 32)],
                         ids=["with_chunk", "verify_runs"])
def test_self_drafting_step_is_one_program_with_its_pools_in_place(
        latent_step, t, nb):
    """The self-drafting step of ``glm-5-ep16-l5`` (five layers and the
    module at the published widths): trunk, the argmax at both rows of
    the verify runs, accept, module and next draft compile as ONE
    program whose two pools (six cache layers: the trunk's five and the
    module's) are donated, come back in place and are never copied
    whole or by layer; the one result is ``s32[4, slots]``, and the
    result of the step before is an operand of the same shape beside the
    index buffer (a run launched ahead reads its tokens and its
    position's shift there: no second program for it); the index
    kernel runs in all six layers; no stack of expert matrices is sliced
    out as a value of its own."""
    import re

    rows = 1024 * 128
    _, _, (ck, cv, _), compiled = latent_step("glm-5-ep16-l5", t, nb)
    assert ck.shape == (6, rows, 640) and cv.shape == (6, rows, 128)
    text = compiled.as_text()
    assert len(_aliased_outputs(text)) == 2
    assert "s32[4,33]" in text
    entry = text[text.index("ENTRY "):]
    operands = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(", entry)
    assert operands.count("s32[4,33]") == 1, operands
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2 ** 30, mem
    assert text.count("latent_index_scores") >= 3   # trunk segments, module
    _assert_latent_buffers_stay(text, (ck, cv), _GLM5_EXPERTS, by_layer=True)


@pytest.mark.parametrize("model,t,nb", [
    ("glm-5-ep16-l5", 32, 32), ("glm-5-ep16-l5", 1024, 64),
    ("dots3-note-ep8-l5", 1024, 64), ("dots3-note-ep8-l5", 16, 64)],
    ids=["glm5_verify_runs", "glm5_with_chunk", "dots3_full_step",
         "dots3_decode_only"])
def test_latent_step_streams_the_head_shaped_query_weights(latent_step, model,
                                                           t, nb):
    """``wq_b``'s and ``idx_wq``'s products (``latent._project``,
    ``_index_inputs``) read their stacked weights from HBM inside the
    fusion that multiplies, in every block of the step: the layer scans'
    bodies (GLM-5's four expert layers, dots3's three window layers) and
    the blocks of their own in the entry computation (GLM-5's dense layer
    0, dots3's two full layers).  With the heads' reshape folded into the
    product the compiler wants the weight ``[out][in]``: it slices the
    layer's matrix out of the stack, transposes the copy and only then
    multiplies: ``bf16[1,2048,16384]`` sliced and copied in the scan's
    body, ``bf16[16384,2048]`` in the entry computation, 67 MB moved twice
    before it is read once (PERF.md, PR 49: a fifth of ``reason_open``'s
    device time).  No instruction that is a buffer of its own (not one
    inside a fusion, which is the streaming read itself) has the shape of
    ONE layer of either weight, in either index order."""
    import collections
    import re

    cfg, params, _, compiled = latent_step(model, t, nb)
    comps = _computations(compiled.as_text())
    fused = {m.group(1) for _, lines in comps.values() for ln in lines
             if (m := re.search(r" fusion\(.*calls=%([^\s,]+)", ln))}
    # stack -> blocks that multiply out of it: the full layers' in two
    # (GLM-5: the scan's body and layer 0; dots3: its two full layers),
    # dots3's window layers' in their scan's body
    layers = params["layers"]
    blocks = {layers["full"]["wq_b"].shape: 2,
              layers["full"]["idx_wq"].shape: 2}
    if cfg.mla.has_window(cfg.num_layers):
        blocks[layers["window"]["wq_b"].shape] = 1
    one_layer = set()
    for n, k, o in blocks:
        assert n > 1
        one_layer |= {f"bf16[1,{k},{o}]", f"bf16[1,{o},{k}]",
                      f"bf16[{o},{k}]"}
    moved, streamed = [], collections.Counter()
    for name, (_, lines) in comps.items():
        if name in fused:
            continue
        for ln in lines:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (\S+?)[{ ]\S* ?([\w-]+)\(", ln)
            # (a ``slice-done`` or ``copy-done`` is the compiler fetching a
            # buffer ahead to its faster memory as it lies, with or
            # without this: dots3's ``idx_wq``, 17 MB a layer)
            if m and m.group(1) in one_layer and not m.group(2).endswith(
                    "-done"):
                moved.append(ln.strip()[:160])
            m = re.search(r" fusion\(.*calls=%([^\s,]+)", ln)
            if m and any(" convolution(" in x for x in comps[m.group(1)][1]):
                streamed.update(re.findall(r": (bf16\[[\d,]+\])",
                                           comps[m.group(1)][0]))
    assert not moved, moved
    want = collections.Counter(
        {"bf16[%d,%d,%d]" % shape: n for shape, n in blocks.items()})
    assert not want - streamed, (want, streamed)


# (preset, its overrides, pool rows, the self-drafting step?, context pages)
_LATENT_READS = {
    "glm5_8k": ("glm-5-ep16", {"num_layers": 5, "first_k_dense": 1},
                1024 * 128, True, 64),
    "dots3_16k": ("dots3-note-prev-ep8", {"num_layers": 5}, 2048 * 128,
                  False, 128),
    "dots3_32k": ("dots3-note-prev-ep8", {"num_layers": 5}, 2048 * 128,
                  False, 256),
}


@pytest.mark.parametrize("case,walked", [
    ("glm5_8k", True), ("dots3_16k", True), ("dots3_32k", False)])
def test_latent_step_reads_by_the_one_path_its_shapes_choose(
        chip, case, walked, monkeypatch):
    """The 1024-row step program of both latent cells at their published
    widths, traced as on a TPU (``latent.on_tpu``: the indexer's kernel
    and ``latent_read.walks`` choose).  At a walked bucket (GLM-5's
    widest, 8k; dots3's 16k) it holds the ``latent_read_walk`` kernel in
    every full layer and no temporary of ``rows x index_topk x row``
    elements, and the pools stay in place; at dots3's 32k bucket it holds
    the gather's temporary and no call of the kernel: one program, one
    read."""
    import re

    from deepspeed_tpu.inference.v2 import latent
    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.inference.v2.ragged import PackedIndex
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models import transformer as tf_model
    from deepspeed_tpu.ops.pallas import latent_read

    preset, over, rows, drafting, nb = _LATENT_READS[case]
    monkeypatch.setattr(latent, "on_tpu", lambda: True)
    cfg = get_model_config(preset, param_dtype=BF16, dtype=BF16, **over)
    t, bs, heads = 1024, 128, cfg.mla.full.num_heads
    assert latent_read.walks(t, nb * bs, cfg.mla.index_topk, heads) is walked
    assert latent.read_impl_name(cfg, t, nb * bs) == (
        "latent_read_walk" if walked else "latent_read_gather")
    params = _abstract(chip, jax.eval_shape(
        lambda k: tf_model.init_params(cfg, k), jax.random.PRNGKey(0)))
    ck, cv, state = jax.eval_shape(lambda: latent.new_cache(cfg, rows, 32, t))
    ck, cv = _abstract(chip, (ck, cv))
    index = PackedIndex(chip((PackedIndex.size(t, 33, nb, drafting),), I32),
                        t, 33, nb, drafting)
    with jax.default_matmul_precision("default"):
        if drafting:
            fn = functools.partial(v2_model.ragged_draft_step, cfg=cfg,
                                   block_size=bs)
            compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
                params, ck, cv, index, chip((4, 33), I32)).compile()
            held, experts = (ck, cv), _GLM5_EXPERTS
        else:
            state = _abstract(chip, state)
            fn = functools.partial(v2_model.ragged_step_sampled, cfg=cfg,
                                   block_size=bs, greedy=True)
            compiled = jax.jit(fn, donate_argnums=(1, 2),
                               donate_argnames=("state",)).lower(
                params, ck, cv, index, chip((33,), I32),
                chip((2,), jnp.uint32), chip((), F32),
                state=state).compile()
            held, experts = (ck, cv, state["win"]), _DOTS3_EXPERTS
    text = compiled.as_text()
    assert "latent_index_scores" in text
    # every query's gathered rows, a block of queries at a time
    gathered = re.findall(r"bf16\[\d+,%d,640\]" % cfg.mla.index_topk, text)
    if walked:
        # the trunk's segments (and the module): one traced body each
        assert text.count("latent_read_walk") >= (3 if drafting else 2)
        assert not gathered, gathered[:3]
        assert len(_aliased_outputs(text)) == len(held)
        _assert_latent_buffers_stay(text, held, experts, by_layer=drafting)
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    else:
        assert "latent_read_walk" not in text
        assert gathered


@pytest.mark.parametrize("t", [256, 32], ids=["full_step", "bucket_32"])
def test_paged_qblock_group_of_five(chip, t):
    """Falcon-H1-34B's attention heads: 20 query heads on 4 key/value
    heads of 128, a group that is no power of two (160 query rows a KV
    head and block, a one-row run's five inside a 32-row window of them),
    pages of 16, 64 pages a sequence."""
    assert paged_attention.narrow_rows(5, 160) == 32

    def fn(q, k, v, tables, pos, clen, slot):
        return paged_attention.paged_decode_attention(
            q, k, v, tables, pos, clen, block_size=_BS,
            sm_scale=128 ** -0.5, token_slot=slot)

    pool = chip((4, 4352 * _BS, 128), BF16)
    _compile(fn, chip((t, 20, 128), BF16), pool, pool, chip((65, 64), I32),
             chip((t,), I32), chip((t,), I32), chip((t,), I32))


def test_paged_qblock_sixteen_heads_a_kv_head_bucket_128(chip):
    """Nemotron-3-Nano's one attention layer: 32 query heads on 2
    key/value heads of 128 (512 query rows a KV head and block, a one-row
    run's sixteen a 16-row window), the 128-row bucket its 60 decoding
    streams take, pages of 16, 256 a sequence, 256 sequences tracked."""
    def fn(q, k, v, tables, pos, clen, slot, layer):
        return paged_attention.paged_decode_attention(
            q, k, v, tables, pos, clen, block_size=_BS,
            sm_scale=128 ** -0.5, token_slot=slot, layer=layer)

    pool = chip((1, 2, 4096 * _BS, 128), BF16)
    _compile(fn, chip((128, 32, 128), BF16), pool, pool,
             chip((257, 256), I32), chip((128,), I32), chip((128,), I32),
             chip((128,), I32), chip((), I32))


def test_paged_decode_int8_kv(chip):
    def fn(q, k, v, pages, pos, clen, ks, vs):
        return paged_attention.paged_decode_attention(
            q, k, v, pages, pos, clen, block_size=_BS, sm_scale=128 ** -0.5,
            window=4096, k_scales=ks, v_scales=vs)

    _compile(fn, *_paged_args(chip, 128, I8), chip((_NKV, _ROWS), F32),
             chip((_NKV, _ROWS), F32))


def test_paged_decode_head_dim_64_is_refused(chip):
    """Every GPT-2 / OPT / Bloom / GPT-Neo width: Mosaic refuses the
    64-wide page slice, and ``supports()`` says so first, so the module
    registry names ``paged_xla`` for them."""
    assert not paged_attention.supports(_BS, 64)
    fn = functools.partial(paged_attention.paged_decode_attention,
                           block_size=_BS, sm_scale=64 ** -0.5)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(fn, *_paged_args(chip, 64, BF16))


@pytest.mark.parametrize("shape", [(50304, 1024), (1024, 4096)],
                         ids=["embedding", "mlp"])
def test_fused_adamw_leaf(chip, shape):
    assert fused_optimizer.supports(shape)
    leaf = chip(shape, F32)
    _compile(fused_optimizer.fused_adamw_leaf, leaf, leaf, leaf, leaf,
             chip((), F32), chip((), I32))


def test_quantize(chip):
    assert quantize.supports((8192, 8192), 256, True, 8)
    _compile(quantize.quantize, chip((8192, 8192), BF16))


def test_ring_flash_carry_block(chip):
    """One ring-attention hop at mistral-7b heads, 2048-token shard."""
    b, hq, hkv, d = 1, 32, 8, 128
    s = flash_mha_mod.ring_carry_pad(2048)
    stat = chip((b, hq, s, 128), F32)
    _compile(flash_mha_mod.flash_carry_block,
             chip((b, hq, s, d), BF16), chip((b, hkv, s, d), BF16),
             chip((b, hkv, s, d), BF16), stat, stat, chip((b, hq, s, d), F32),
             chip((), I32), chip((), I32))


def test_pallas_matmul_kernel(chip, monkeypatch):
    """The kernel behind the fused gather-matmul; its gate reads the live
    backend (the CPU, here), so the test opens it."""
    monkeypatch.setattr(gather_matmul, "on_tpu", lambda: True)
    _compile(gather_matmul.pallas_matmul, chip((8192, 1024), BF16),
             chip((1024, 4096), BF16))


# -- kernel names: what the profiler's ``XLA Ops`` line shows ---------------
def _kernel_names(text):
    """Instruction names of the Mosaic kernels in a compiled program."""
    return [line.split("=")[0].strip().lstrip("%").strip()
            for line in text.splitlines()
            if "tpu_custom_call" in line and " = " in line]


def test_flash_kernel_names_survive_checkpoint(chip):
    """Each flash kernel is an instruction under its own name, not the
    wrapper's (``checkpoint.N``, ``closed_call.N``): under
    ``jax.checkpoint`` the forward runs again inside the backward."""
    b, hq, hkv, s, d, window = FLASH_SHAPES["gpt2-350m"]

    @jax.checkpoint
    def fwd(q, k, v):
        return flash_mha_mod.flash_mha(q, k, v, True, None, window)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(F32))

    args = (chip((b, hq, s, d), BF16), chip((b, hkv, s, d), BF16),
            chip((b, hkv, s, d), BF16))
    names = _kernel_names(_compile(jax.grad(loss, argnums=(0, 1, 2)), *args))
    for want in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(n.split(".")[0] == want for n in names), (want, names)
    assert all(n.startswith("flash_") for n in names), names
    fwd_names = _kernel_names(_compile(fwd, *args))
    assert [n.split(".")[0] for n in fwd_names] == ["flash_fwd"]


def _pallas_calls(jaxpr, out=None):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, out)
    return out


@pytest.mark.parametrize("name", ["gpt2-350m", "opt-1.3b"])
def test_flash_kernels_execute_what_plan_says(chip, name):
    """The two train cells' shapes: three kernels under the ledger's
    names, and the score products INSIDE them cover the pairs ``plan``
    counts and no more — a later edit cannot quietly bring the whole
    [bq, S] score block back (it would read S² here, as it did before PR
    47).  A score product is a ``[rows, d] x [keys, d]`` contraction: one
    a pair in the forward, two (scores and ``do v^T``) in dq and dkv."""
    b, hq, hkv, s, d, window = FLASH_SHAPES[name]
    p = flash_mha_mod.plan(s, d, hq // hkv, True, window)
    assert {p.fwd.path, p.dq.path, p.dkv.path} == {"live"}

    def loss(q, k, v):
        return jnp.sum(flash_mha_mod.flash_mha(q, k, v, True, None,
                                               window).astype(F32))

    args = (chip((b, hq, s, d), BF16), chip((b, hkv, s, d), BF16),
            chip((b, hkv, s, d), BF16))
    grad = jax.grad(loss, argnums=(0, 1, 2))
    names = _kernel_names(_compile(grad, *args))
    assert len(names) == 3, names       # (a bare grad wraps them: jvp_...)
    for want in ("flash_fwd", "flash_bwd_dq_", "flash_bwd_dkv"):
        assert sum(want in n + "_" for n in names) == 1, (want, names)
    executed = {}
    for eqn in _pallas_calls(jax.make_jaxpr(grad)(*args).jaxpr):
        pairs = 0
        for inner in eqn.params["jaxpr"].eqns:
            if (inner.primitive.name == "dot_general"
                    and inner.params["dimension_numbers"]
                    == (((1,), (1,)), ((), ()))):
                rows, keys = inner.outvars[0].aval.shape
                assert keys < s, (eqn.params["name"], rows, keys)
                pairs += rows * keys
        executed[eqn.params["name"]] = pairs
    assert executed == {"flash_fwd": p.fwd.executed_pairs,
                        "flash_bwd_dq": 2 * p.dq.executed_pairs,
                        "flash_bwd_dkv": 2 * p.dkv.executed_pairs}
    assert p.fwd.executed_pairs <= 0.65 * s * s


@pytest.mark.parametrize("kv_dtype,want", [(BF16, "paged_qblock"),
                                           (I8, "paged_decode_q8")])
def test_paged_kernel_name(chip, kv_dtype, want):
    def fn(q, k, v, pages, pos, clen, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention.paged_decode_attention(
            q, k, v, pages, pos, clen, block_size=_BS, sm_scale=128 ** -0.5,
            window=4096, k_scales=ks, v_scales=vs)

    extra = ((chip((_NKV, _ROWS), F32),) * 2 if kv_dtype == I8 else ())
    names = _kernel_names(_compile(fn, *_paged_args(chip, 128, kv_dtype),
                                   *extra))
    assert [n.split(".")[0] for n in names] == [want], names
