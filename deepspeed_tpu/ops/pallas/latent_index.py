"""Pallas kernel for a learned sparse-attention indexer over paged keys
(DeepSeek-V3.2's "lightning indexer", as ``dots3_note`` takes it): for
every row ``t`` of a ragged step and every position ``c`` of its own
sequence's context,

    I[t, c] = sum_j w[t, j] * relu(q[t, j] . k[c])

over ``j`` index heads, with ``k`` one ``d``-wide key a token in pages of
a pool ``[L, P, d]`` (``inference/v2/latent.py`` writes them where the
latent rows go).  What attention then reads are each row's largest
``I[t, :]``; this kernel makes the scores and nothing else.

``latent_index_scores``, built like ``paged_qblock``
(``ops/pallas/paged_attention.py``):

* grid ``(T / QB,)``: a program serves ``QB`` consecutive rows, cuts them
  into RUNS of one sequence's rows (``token_slot``, at run time) and walks
  each run's pages once, from page 0 to the causal frontier of its last
  row, ``step_keys`` keys a step, whole pages by double-buffered DMA out
  of the HBM-resident pool;
* a step multiplies ``HEAD_GROUP`` heads' queries at once, ``[G * QB, d]
  x [d, step_keys]`` (rows head-major, so a head's ``QB`` rows are one
  sublane slab), applies the ReLU and the rows' weights and adds the
  group's slabs: no ``[rows, heads, keys]`` tensor leaves VMEM;
* scores land in the program's ``[QB, C]`` float32 output block (``C`` the
  step's context bucket), ``-inf`` wherever a key is not causally visible
  to the row, beyond its context, or never walked.

The XLA form it replaces gathers every row's keys, ``[T, C, d]``: 8 GiB
for a 1024-row chunk at a 32k context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False

QUERY_BLOCK = 32        # rows of the step a program serves
HEAD_GROUP = 8          # index heads one matmul takes
_STEP_KEYS = 512        # keys a compute step takes (whole pages)
_VMEM_LIMIT = 64 * 1024 * 1024


def supports(block_size: int, d: int) -> bool:
    """Pages must be whole sublane tiles and keys whole lanes (a page's
    DMA slices ``[block_size, d]`` out of the pool), and a compute step
    whole pages."""
    return (block_size >= 8 and block_size & (block_size - 1) == 0
            and d % 128 == 0)


def _kernel(tables_ref, slot_ref, pos_ref, clen_ref, layer_ref, q_ref, w_ref,
            rowpos_ref, rowclen_ref, k_hbm, o_ref, k_buf, sem, *, bs, qb,
            heads, group, pages_per_step):
    base = pl.program_id(0) * qb
    layer = layer_ref[0]
    step_keys = pages_per_step * bs
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (qb, 1), 0)
    row_pos = rowpos_ref[...]                                # [qb, 1]
    row_clen = rowclen_ref[...]

    def walk(r0, r1, seq, pmax, cmax):
        """Rows [r0, r1) of the block belong to table row ``seq``."""
        j_hi = jnp.where(cmax > 0, pmax // bs + 1, 0)
        n_steps = (j_hi + pages_per_step - 1) // pages_per_step

        def page_copy(buf, p, page):
            return pltpu.make_async_copy(
                k_hbm.at[layer, pl.dslice(page * bs, bs)],
                k_buf.at[buf, pl.dslice(pl.multiple_of(p * bs, bs), bs)],
                sem.at[buf])

        def start_step(n, buf):
            def start_page(p, _):
                # past the frontier: the last live page again (its keys
                # lie beyond every row's position, so they are masked)
                j = jnp.minimum(n * pages_per_step + p, j_hi - 1)
                page_copy(buf, p, tables_ref[seq, j]).start()
                return 0

            lax.fori_loop(0, pages_per_step, start_page, 0)

        def wait_step(buf):
            def wait_page(p, _):
                page_copy(buf, p, 0).wait()
                return 0

            lax.fori_loop(0, pages_per_step, wait_page, 0)

        @pl.when(n_steps > 0)
        def _():
            start_step(0, 0)

        in_run = (row >= r0) & (row < r1)

        def body(n, _):
            buf = lax.rem(n, 2)

            @pl.when(n + 1 < n_steps)
            def _():
                start_step(n + 1, 1 - buf)

            wait_step(buf)
            k = k_buf[buf]                                   # [keys, d]

            def head_group(g, acc):
                rows = pl.dslice(pl.multiple_of(g * group * qb, group * qb),
                                 group * qb)
                s = lax.dot_general(
                    q_ref[rows, :], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [G*qb, keys]
                s = jnp.maximum(s, 0.0) * w_ref[rows, :]
                for j in range(group):
                    acc = acc + s[j * qb:(j + 1) * qb]
                return acc

            acc = lax.fori_loop(0, heads // group, head_group,
                                jnp.zeros((qb, step_keys), jnp.float32))
            col0 = pl.multiple_of(n * step_keys, step_keys)
            c = lax.broadcasted_iota(jnp.int32, (qb, step_keys), 1) + col0
            seen = in_run & (c <= row_pos) & (c < row_clen)
            cols = pl.dslice(col0, step_keys)
            o_ref[:, cols] = jnp.where(seen, acc, o_ref[:, cols])
            return 0

        lax.fori_loop(0, n_steps, body, 0)

    def row_body(i, run):
        # a run ends where the next row belongs to another sequence
        r0, pmax, cmax = run
        seq = slot_ref[base + i]
        pmax = jnp.maximum(pmax, pos_ref[base + i])
        cmax = jnp.maximum(cmax, clen_ref[base + i])
        last = (i == qb - 1) | (
            slot_ref[base + jnp.minimum(i + 1, qb - 1)] != seq)

        @pl.when(last)
        def _():
            walk(r0, i + 1, seq, pmax, cmax)

        return (jnp.where(last, i + 1, r0), jnp.where(last, -1, pmax),
                jnp.where(last, 0, cmax))

    lax.fori_loop(0, qb, row_body, (jnp.int32(0), jnp.int32(-1),
                                    jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("block_size",))
def index_scores(q_i, w_i, pool, layer, tables, token_slot, token_pos,
                 token_ctx_len, *, block_size: int):
    """q_i: [T, heads, d]; w_i: [T, heads] float32; pool: every layer's
    index keys [L, P, d] and ``layer`` (a traced scalar will do) the one
    to read; tables: [S, NB] page ids, ``token_slot`` [T] each row's table
    row; token_pos / token_ctx_len: [T].  Returns scores
    [T, NB * block_size] float32, ``-inf`` where position ``c`` is not
    visible to the row (``c > token_pos`` or ``c >= token_ctx_len``)."""
    t, heads, d = q_i.shape
    bs, nb = block_size, tables.shape[1]
    ctx = nb * bs
    i32 = lambda a: a.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    qb = min(QUERY_BLOCK, t)
    group = min(HEAD_GROUP, heads)
    pad = -t % qb
    slot, pos, clen = i32(token_slot), i32(token_pos), i32(token_ctx_len)
    if pad:
        # rows of no sequence (slot -1) with no context: walked by nobody
        q_i = jnp.pad(q_i, ((0, pad), (0, 0), (0, 0)))
        w_i = jnp.pad(w_i, ((0, pad), (0, 0)))
        slot = jnp.pad(slot, (0, pad), constant_values=-1)
        pos, clen = jnp.pad(pos, (0, pad)), jnp.pad(clen, (0, pad))
    tp = t + pad
    nblk = tp // qb
    pages_per_step = max(1, min(_STEP_KEYS // bs, nb))
    step_keys = pages_per_step * bs
    if ctx % step_keys:
        raise ValueError(f"a context of {nb} pages of {bs} rows is not "
                         f"whole compute steps of {step_keys} keys")

    # per block, rows head-major: row = head * qb + token of the block
    head_major = lambda a: a.reshape((nblk, qb, heads) + a.shape[2:]) \
        .swapaxes(1, 2).reshape((nblk, heads * qb) + a.shape[2:])
    q_spec = pl.BlockSpec((None, heads * qb, d), lambda b, *refs: (b, 0, 0))
    w_spec = pl.BlockSpec((None, heads * qb, 1), lambda b, *refs: (b, 0, 0))
    col_spec = pl.BlockSpec((qb, 1), lambda b, *refs: (b, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nblk,),
        in_specs=[q_spec, w_spec, col_spec, col_spec,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((qb, ctx), lambda b, *refs: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, step_keys, d), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, qb=qb, heads=heads, group=group,
                          pages_per_step=pages_per_step),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, ctx), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=INTERPRET,
        name="latent_index_scores",
    )(i32(tables), slot, pos, clen, layer,
      head_major(q_i.astype(pool.dtype)),
      head_major(w_i.astype(jnp.float32)[..., None]), pos[:, None],
      clen[:, None], pool)
    return out[:t]
