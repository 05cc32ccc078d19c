"""Pallas kernel for the read of a latent-attention layer's SELECTED rows
(``inference/v2/latent.py:_full_layer``): absorbed attention of every head
of a ragged step's rows over the one shared row ``[c_kv | k_r | zeros]`` a
token keeps in pages of a pool ``[L, P, row]``, the indexer's selection
entering as a MASK ``chosen [T, C]`` over the row's own context and not as
a list of rows to gather.

``latent_read_walk``, built like ``latent_index_scores`` and
``paged_qblock``:

* grid ``(T / QB,)``: a program serves ``QB`` consecutive rows of the
  step, cuts them into RUNS of one sequence (``token_slot``, at run time)
  and walks each run's own pages once, from page 0 to the causal frontier
  of its last row, ``step_keys`` keys a step, whole pages by
  double-buffered DMA out of the HBM-resident pool.  The XLA read it
  replaces gathers ``index_topk`` rows for EVERY query (``[n, K, row]``:
  2.6 MB a query at GLM-5's widths) and multiplies them as a batch of
  matrix-vector products;
* queries are token-major, ``[T * heads, row]`` (a reshape of what the
  projection leaves): the heads of ``group`` consecutive tokens are one
  left operand ``[group * heads, row] x [row, step_keys]``, every head
  reading the one latent row, and only the token groups a run covers are
  multiplied: a decode row of 64 heads costs one such product a step, not
  a block's worth;
* ``chosen`` is the WHOLE mask: it holds a key only where the indexer's
  score was finite (causally visible, inside the context) and among the
  row's best, so the kernel compares no position.  A key no row of the
  run holds is zeroed in the page buffer before the value product: a
  stale row of a page, a refused draft's row or an unselected row never
  reaches the result, whatever it holds (``0 x NaN`` would);
* online softmax (m, l, acc) per row and head in float32 scratch, scores
  float32, products in the pool's dtype; the latent cut ``[..., :rank]``
  is taken out of the accumulator at the end.

The walk multiplies every visible key, the gather ``index_topk`` of them:
:func:`walks` says, from a step program's shapes, which of the two reads
the program carries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

INTERPRET = False

QUERY_BLOCK = 32        # rows of the step a program serves
# (token, head) rows one matmul takes: a decode row's or a verify run's
# heads where the step is such rows alone (a wider product multiplies
# padding), twice that where it holds a chunk (a 1024-row chunk of 64 heads
# at a 4k context: 6.0 ms at 128, 5.0 at 256, 4.7 at 512; of 128 heads at
# 16k: 45.5 ms at 128, 37.3 at 256: PR 44's chip runs, PERF.md)
_GROUP_ROWS = 128
_CHUNK_GROUP_ROWS = 256
_STEP_KEYS = 512        # keys a compute step takes (whole pages)
_VMEM_LIMIT = 64 * 1024 * 1024


# (context position, head) pairs a row may walk for every key the gather
# would fetch it, beyond which a step program of more than one query block
# keeps the gather (:func:`walks`)
_WALK_HEAD_KEYS = 1024


def walks(rows: int, context: int, topk: int, heads: int) -> bool:
    """Whether a step program of ``rows`` rows at a context bucket of
    ``context`` positions reads its full layers' selected rows by the walk
    (else by the gather): decided where the program is traced, from its
    shapes alone, so a program carries one read.

    The walk multiplies every visible key of a run for all ``heads``; the
    gather fetches ``topk`` rows a query, padding rows too, and most of
    its time is the fetch and the copy, whatever the heads.  Measured on
    a v5e with ``index_topk`` 2048 and the 640-lane row, the selection
    included on both sides (PR 44's chip runs, PERF.md section 6, PR 45):

    * a 1024-row chunk whose context fills the bucket, 64 heads: walk 1.2
      / 2.6 / 6.0 / 12.2 ms at 1k / 2k / 4k / 8k against 10.8 / 21.2 /
      22.1 / 22.7 gathered; 128 heads: 11.1 / 22.5 / 45.5 / 96.8 ms at 4k
      / 8k / 16k / 32k against 27.9 / 28.6 / 33.9 / 46.7 (the walk at 128
      rows a product; at 256, as chunks run, 37.3 at 16k), and with the
      context at three quarters of the bucket 34.6 against 33.9 at 16k,
      75.1 against 46.8 at 32k.  The walk is bound by the matrix unit
      (105-150 TFLOP/s of 197), ``context x heads`` a row: it is ahead
      while ``context x heads <= 1024 x topk`` (16k at 128 heads, where a
      bucket's mean context wins and its top loses a tenth; 32k at 64);
    * up to one query block of rows (decode rows, verify runs) the gather
      costs the same whether a row is real or padding, the walk only what
      the real rows' contexts hold: 16 rows of 64 heads 0.35 against 1.12
      ms at 4k, 32 rows in verify runs 0.54 against 1.08 at 8k; 128 heads,
      ALL 16 rows real: 0.53 / 0.99 / 1.71 ms at 8k / 16k / 32k against
      0.62 / 0.69 / 0.73.  A program cannot see how many of its rows are
      real; the cells' decode steps hold 1 to 5 rows at 128 heads (0.05 to
      0.1 ms a row and layer), so such a program walks at any context.
    """
    if context <= topk or rows <= QUERY_BLOCK:
        return True
    return context * heads <= _WALK_HEAD_KEYS * topk


def _kernel(tables_ref, slot_ref, pos_ref, clen_ref, layer_ref, q_ref,
            mask_ref, kv_hbm, o_ref, m_scr, l_scr, acc_scr, kv_buf, mask_scr,
            sem, *, bs, qb, heads, group, pages_per_step, sm_scale, rank):
    """Grid (T / qb,): ``q_ref`` ``[qb * heads, row]`` and ``o_ref``
    ``[qb * heads, rank]``, row = token * heads + head; ``mask_ref``
    ``[qb, C]``; ``kv_hbm`` every layer's pool ``[L, P, row]`` and
    ``layer_ref[0]`` the one to read."""
    base = pl.program_id(0) * qb
    layer = layer_ref[0]
    step_keys = pages_per_step * bs
    f32 = jnp.float32
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, f32)
    l_scr[...] = jnp.zeros(l_scr.shape, f32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, f32)
    row = lax.broadcasted_iota(jnp.int32, (qb, 1), 0)
    ones = jnp.ones((qb, 128), kv_buf.dtype)

    def walk(r0, r1, seq, pmax, cmax):
        """Rows [r0, r1) of the block belong to table row ``seq``."""
        # (lax.div: every operand is whole and not negative where it
        # counts, and ``//`` lowers to a dozen operations each)
        j_hi = lax.select(cmax > 0, lax.div(pmax, bs) + 1, 0)
        n_steps = lax.div(j_hi + pages_per_step - 1, pages_per_step)

        def page_copy(buf, p, page):
            return pltpu.make_async_copy(
                kv_hbm.at[layer, pl.dslice(page * bs, bs)],
                kv_buf.at[buf, pl.dslice(pl.multiple_of(p * bs, bs), bs)],
                sem.at[buf])

        def start_step(n, buf):
            def start_page(p, _):
                # past the frontier: the last live page again (no row
                # holds its keys, so they are zeroed below)
                j = lax.min(n * pages_per_step + p, j_hi - 1)
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
            cols = pl.dslice(pl.multiple_of(n * step_keys, step_keys),
                             step_keys)
            held = mask_ref[:, cols].astype(f32) * in_run
            mask_scr[...] = held
            # keys some row of the run holds, as a column: held^T . ones
            live = lax.dot_general(
                held.astype(ones.dtype), ones, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)[:, :1] > 0       # [keys, 1]
            kv = kv_buf[buf].astype(f32)
            kv_buf[buf] = lax.select(
                lax.broadcast_in_dim(live, kv.shape, (0, 1)), kv,
                lax.full_like(kv, 0)).astype(kv_buf.dtype)

            def token_group(g, _):
                tok0 = g * group
                rows = pl.dslice(
                    pl.multiple_of(tok0 * heads, group * heads),
                    group * heads)
                valid = jnp.concatenate([
                    jnp.broadcast_to(mask_scr[pl.dslice(tok0 + i, 1), :] > 0,
                                     (heads, step_keys))
                    for i in range(group)], axis=0)
                kv = kv_buf[buf]                             # [keys, row]
                s = lax.dot_general(
                    q_ref[rows, :], kv, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32)           # [G*heads, keys]
                s = lax.select(valid, s * sm_scale, lax.full_like(s, NEG_INF))
                m_prev = m_scr[rows, :]
                m_new = lax.max(m_prev, lax.reduce_max(s, (1,))[:, None])
                alpha = lax.exp(m_prev - m_new)
                # a row that holds no key of this step adds nothing:
                # without the select its exp(NEG_INF - NEG_INF) would be 1
                p = lax.select(valid, lax.exp(s - m_new), lax.full_like(s, 0))
                l_scr[rows, :] = l_scr[rows, :] * alpha + lax.reduce_sum(
                    p, (1,))[:, None]
                acc_scr[rows, :] = acc_scr[rows, :] * alpha + lax.dot_general(
                    p.astype(kv.dtype), kv, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)             # [G*heads, row]
                m_scr[rows, :] = m_new
                return 0

            lax.fori_loop(lax.div(r0, group), lax.div(r1 - 1, group) + 1,
                          token_group, 0)
            return 0

        lax.fori_loop(0, n_steps, body, 0)

    def row_body(i, run):
        # a run ends where the next row belongs to another sequence
        r0, pmax, cmax = run
        seq = slot_ref[base + i]
        pmax = lax.max(pmax, pos_ref[base + i])
        cmax = lax.max(cmax, clen_ref[base + i])
        last = (i == qb - 1) | (
            slot_ref[base + lax.min(i + 1, qb - 1)] != seq)

        @pl.when(last)
        def _():
            walk(r0, i + 1, seq, pmax, cmax)

        return (lax.select(last, i + 1, r0), lax.select(last, -1, pmax),
                lax.select(last, 0, cmax))

    lax.fori_loop(0, qb, row_body, (jnp.int32(0), jnp.int32(-1),
                                    jnp.int32(0)))
    l = l_scr[...]
    o_ref[...] = (acc_scr[:, :rank] / lax.select(l > 0, l, lax.full_like(l, 1))
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "rank", "scale"))
def latent_read(q, chosen, pool, layer, tables, token_slot, token_pos,
                token_ctx_len, *, block_size: int, rank: int, scale: float):
    """q: [T, heads, row] absorbed queries at the pool's row width;
    chosen: [T, NB * block_size] bool, the context positions of its own
    sequence each row reads (never one its indexer scored ``-inf``); pool:
    every layer's latent rows [L, P, row] and ``layer`` (a traced scalar
    will do) the one to read; tables: [S, NB] page ids, ``token_slot`` [T]
    each row's table row; token_pos / token_ctx_len: [T], which bound the
    walk.  Returns the attended latents [T, heads, rank] in the pool's
    dtype, zeros for a row that holds no key."""
    t, heads, width = q.shape
    bs, nb = block_size, tables.shape[1]
    ctx = nb * bs
    i32 = lambda a: a.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    qb = QUERY_BLOCK
    group_rows = _GROUP_ROWS if t <= qb else _CHUNK_GROUP_ROWS
    group = 1       # tokens a matmul takes: a power of two, like ``qb``
    while 2 * group * heads <= group_rows and 2 * group <= qb:
        group *= 2
    pad = -t % qb
    slot, pos, clen = i32(token_slot), i32(token_pos), i32(token_ctx_len)
    mask = chosen.astype(jnp.int8)
    if pad:
        # rows of no sequence (slot -1) with no context: walked by nobody
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
        slot = jnp.pad(slot, (0, pad), constant_values=-1)
        pos, clen = jnp.pad(pos, (0, pad)), jnp.pad(clen, (0, pad))
    tp = t + pad
    pages_per_step = max(1, min(_STEP_KEYS // bs, nb))
    step_keys = pages_per_step * bs
    if ctx % step_keys:
        raise ValueError(f"a context of {nb} pages of {bs} rows is not "
                         f"whole compute steps of {step_keys} keys")

    rows = qb * heads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(tp // qb,),
        in_specs=[pl.BlockSpec((rows, width), lambda b, *refs: (b, 0)),
                  pl.BlockSpec((qb, ctx), lambda b, *refs: (b, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, rank), lambda b, *refs: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, width), jnp.float32),
            pltpu.VMEM((2, step_keys, width), pool.dtype),
            pltpu.VMEM((qb, step_keys), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, qb=qb, heads=heads, group=group,
                          pages_per_step=pages_per_step, sm_scale=scale,
                          rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp * heads, rank), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=INTERPRET,
        name="latent_read_walk",
    )(i32(tables), slot, pos, clen, layer,
      q.astype(pool.dtype).reshape(tp * heads, width), mask, pool)
    return out.reshape(tp, heads, rank)[:t]
