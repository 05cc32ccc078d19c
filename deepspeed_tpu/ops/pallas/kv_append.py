"""Pallas append of a ragged step's new KV rows to their pages, both pools
in one call.

The step's rows reach the pools ``[L, nkv, P, d]`` (K rows and V rows
may each have a width of their own, whole 128-lane tiles) by WHOLE PAGES: a
sequence's new rows are consecutive positions, so they fill consecutive
rows of consecutive pages, and a page of 16 rows of bfloat16 is one packed
tile.  The scatter this replaces made an update of ``d`` elements for every
(row, head): 2,048 of them a pool, layer and 256-row step, about 115 us
each pool and layer (PERF.md, PR 30 and PR 41).

* :func:`page_list` cuts the step's rows, inside the jitted program and
  once for all its layers, into ENTRIES: the consecutive rows that land in
  one page (the page, the source row that meets its row 0, the page's
  first and one-past-last new row).  Padding rows aim at page 0, the
  garbage page, as they always did: a stretch of them is an entry that
  writes that page, which nobody reads.
* ``kv_append`` is ONE call a layer for both pools (half the lowering
  and launch cost of a call a pool).  The pools stay in HBM and come back
  aliased.  A program takes a block of the step's rows of K and of V (a
  step of the serving cells is one program) and spreads them in VMEM as
  float32, a head's lane tile apart: a value rounded to the pool's dtype
  before is exact there, and a load takes 32-bit rows at any sublane
  offset where a packed bfloat16 row is half a word.  Its entries go
  through in groups: every page of the group is fetched ``[nkv, bs, d]``
  by one strided DMA a pool, all in flight together, each on a semaphore
  of its own; each takes its new rows under a row mask and is written
  back.  Pages of one step are distinct but for page 0 (a page belongs to
  one sequence and a sequence's rows are one run), so the copies of a
  group never meet; programs run one after another.
* :func:`fit` sizes the block of rows and the group from the shapes it
  is handed (kv heads, head, page, dtype) to a budget of VMEM; a shape of
  which not one page fits beside sixteen rows has no kernel, and the
  caller keeps the row scatter.

After the call every page but page 0 holds, bit for bit, what the scatter
put there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import paged_attention

# The most rows of the step one program takes, and the most pages it
# fetches, merges and writes back together: the serving cells' shapes
# (4 and 8 kv heads of 128, pages of 16 rows) take both in 6.4 MB.
ROW_BLOCK = 256
_GROUP = 32
_LANES = 128
# What the call may hold of VMEM (a v5e has 128 MiB; the compiler's own
# default is 16), and what :func:`fit` sizes its buffers to: the rest is
# the compiler's, for the values of a merge in flight.
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = 24 * 1024 * 1024


def append_pages(items, block_size: int) -> int:
    """Destination pages the real rows of a step touch, from its
    ``(cached, n_new)`` work items: :func:`page_list` makes an entry of
    each (and of a page cut between two blocks of rows two)."""
    return sum((cached + n - 1) // block_size - cached // block_size + 1
               for cached, n in items if n > 0)


def fit(t: int, nkv: int, d: int, block_size: int, dtype,
        dv: int | None = None):
    """``(rows, group)``: the rows of a ``t``-row step one program takes
    and the pages it holds at once, for pools of ``dtype`` with ``nkv``
    heads, K rows of ``d`` and V rows of ``dv`` (``None``: ``d``) and
    pages of ``block_size`` rows, inside the VMEM budget: half of it for the pages (both pools), the rest for the rows
    (both pools: the block as it comes, twice, for the pipeline fetches
    the next behind the one at work, and once spread as float32 with a
    page of room at either end).  None where not a page fits beside
    sixteen rows: there is no kernel for the shape."""
    size = jnp.dtype(dtype).itemsize
    both = d + (d if dv is None else dv)
    a_page = nkv * block_size * both * size
    a_row = nkv * both * (2 * size + 4)
    group = min(_GROUP, _VMEM_BUDGET // 2 // a_page)
    room = _VMEM_BUDGET - group * a_page - 2 * block_size * nkv * both * 4
    rows = ROW_BLOCK
    while rows > 16 and rows * a_row > room:
        rows //= 2
    if group < 1 or rows * a_row > room:
        return None
    rows = min(rows, t)
    return rows, min(group, rows)       # an entry is a row at the least


def step_pages(pool, token_dest, block_size: int, v_pool=None):
    """:func:`page_list` of a step's destinations, cut as :func:`kv_append`
    takes it for pools like ``pool`` [L, nkv, P, d] (and ``v_pool``
    [L, nkv, P, dv] where V rows have a width of their own; the same for
    every layer of a step: made once); None where :func:`fit` has no
    kernel for the shape."""
    plan = fit(token_dest.shape[0], pool.shape[1], pool.shape[3], block_size,
               pool.dtype, None if v_pool is None else v_pool.shape[3])
    return None if plan is None else page_list(token_dest, block_size,
                                               plan[0])


@functools.partial(jax.jit, static_argnames=("block_size", "row_block"))
def page_list(token_dest, block_size: int, row_block: int):
    """The step's rows by destination page.  ``token_dest`` [T]: each
    row's flat pool row (page * block_size + row in the page; page 0 is
    the garbage page).  An entry is a run of consecutive rows with
    consecutive destinations inside one page and one block of
    ``row_block`` rows; the rows aimed at page 0 between or behind such
    runs make entries of their own, which write that page only.  Returns
    ``(ends, row0, base, lo, hi)``: ``ends[g]`` entries start before row
    block ``g`` ends, and per entry, in row order (``[T]``; those past the
    last are never read), the pool row its page starts at, the row of its
    block that meets the page's row 0 (plus ``block_size``, the room
    :func:`kv_append` leaves in front), and the page's rows ``[lo, hi)``
    that are new.  Plain ``lax`` operations, few: every step program
    lowers them again (PERF.md, PR 41)."""
    t = token_dest.shape[0]
    bs = block_size
    i32 = jnp.int32
    dest = token_dest.astype(i32)
    rows = lax.iota(i32, t)
    live = dest >= bs
    before = lax.pad(dest[:-1], i32(-2), [(1, 0, 0)])     # the row above's
    joins = (dest == before + 1) & (lax.rem(dest, bs) != 0)
    if t > row_block:
        joins &= lax.rem(rows, row_block) != 0
    start = lax.select(live, ~joins, before >= bs)
    entry = lax.cumsum(start.astype(i32))               # 1-based, by row
    # the e-th entry's first row: as many rows lie before it as belong to
    # the entries up to e
    across = lambda x: lax.broadcast_in_dim(x, (t, t), (1,))
    down = lambda x: lax.broadcast_in_dim(x, (t, t), (0,))
    first = (across(entry) <= down(rows)).astype(i32).sum(axis=1)
    dest0 = lax.select(across(rows) == down(first), across(dest),
                       jnp.zeros((t, t), i32)).sum(axis=1)
    count = lax.pad(first[1:], i32(t), [(0, 1, 0)]) - first
    lo = lax.rem(dest0, bs)
    ends = entry[-1:] if t <= row_block else jnp.concatenate(
        [entry[row_block - 1:-1:row_block], entry[-1:]])
    return (ends, dest0 - lo, lax.rem(first, row_block) - lo + bs, lo,
            lo + count)


def _kernel(ends_ref, layer_ref, row0_ref, base_ref, lo_ref, hi_ref, k_ref,
            v_ref, k_in, v_in, k_out, v_out, k_rows, v_rows, k_buf, v_buf,
            sem_in, sem_out, *, bs, group):
    """Grid (row blocks,): ``k_ref`` / ``v_ref`` a block of the step's
    rows ``[rows, nkv * d]``; ``k_out`` / ``v_out`` the pools ``[L, nkv,
    P, d]`` in HBM, read and written (the operands they alias, ``k_in`` /
    ``v_in``, are the same memory on the chip and not touched: a page cut
    between two programs is read as the first left it); ``k_rows`` /
    ``v_rows`` ``[row width / lanes, nkv, rows + 2 bs, lanes]`` float32;
    ``k_buf`` / ``v_buf`` ``[group, nkv, bs, row width]`` (K rows and V
    rows each of a width of their own); ``sem_in`` / ``sem_out`` ``[2,
    group]``, one a pool and buffer: a wait on a semaphore that several
    copies in flight signal proves that as many bytes came, not whose."""
    del k_in, v_in
    g = pl.program_id(0)
    layer = layer_ref[0]
    e_lo = lax.select(g > 0, ends_ref[lax.max(g - 1, 0)], 0)
    e_hi = ends_ref[g]
    t = k_ref.shape[0]
    _, nkv, _, lane = k_rows.shape
    # (the step's rows, their spread, the pages' buffer) a pool
    pools = ((k_ref, k_rows, k_buf), (v_ref, v_rows, v_buf))
    most_tiles = max(k_rows.shape[0], v_rows.shape[0])

    # the block's rows as 32-bit values, a head's lane tile apart, with a
    # page of room at either end that is never read unmasked
    def spread(h, _):
        for c in range(most_tiles):
            of_row = {}     # by a row's tiles: one slice where K and V agree
            for ref, rows, _ in pools:
                tiles = rows.shape[0]
                if c >= tiles:
                    continue
                if tiles not in of_row:
                    of_row[tiles] = pl.dslice(
                        pl.multiple_of((h * tiles + c) * lane, lane), lane)
                rows[c, h, pl.ds(bs, t), :] = ref[:, of_row[tiles]].astype(
                    jnp.float32)
        return 0

    lax.fori_loop(0, nkv, spread, 0)
    row = lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1)

    def copies(e, j, fetch):
        page = pl.dslice(pl.multiple_of(row0_ref[e], bs), bs)
        pairs = ((k_out.at[layer, :, page], k_buf.at[j]),
                 (v_out.at[layer, :, page], v_buf.at[j]))
        sem = sem_in if fetch else sem_out
        return [pltpu.make_async_copy(*(p if fetch else p[::-1]),
                                      sem.at[i, j])
                for i, p in enumerate(pairs)]

    def merge(e, j):
        new = jnp.broadcast_to((row >= lo_ref[e]) & (row < hi_ref[e]),
                               k_buf.shape[1:3] + (lane,))
        src = pl.dslice(base_ref[e], bs)
        for c in range(most_tiles):
            lanes = slice(c * lane, (c + 1) * lane)
            for _, rows, buf in pools:
                if c < rows.shape[0]:
                    buf[j, :, :, lanes] = lax.select(
                        new, rows[c, :, src, :].astype(buf.dtype),
                        buf[j, :, :, lanes])

    def one_group(c, _):
        e0 = e_lo + c * group
        m = lax.min(group, e_hi - e0)

        def fetch(j, _):
            for copy in copies(e0 + j, j, True):
                copy.start()
            return 0

        def put(j, _):
            for copy in copies(e0 + j, j, True):
                copy.wait()
            merge(e0 + j, j)
            for copy in copies(e0 + j, j, False):
                copy.start()
            return 0

        def done(j, _):
            for copy in copies(e0 + j, j, False):
                copy.wait()
            return 0

        for phase in (fetch, put, done):
            lax.fori_loop(0, m, phase, 0)
        return 0

    lax.fori_loop(0, lax.div(e_hi - e_lo + group - 1, group), one_group, 0)


def kv_append(cache_k, cache_v, k, v, pages, layer, block_size: int):
    """Put the step's rows ``k`` [T, nkv, d] and ``v`` [T, nkv, dv] into
    ``layer``'s pages of the pools ``cache_k`` [L, nkv, P, d] and
    ``cache_v`` [L, nkv, P, dv], in place (donate the pools), by whole pages of ``block_size`` rows: ``(cache_k',
    cache_v')``.  ``pages``: :func:`step_pages` of the rows' destinations
    for these pools.  Pages a step touches are distinct but for page 0: a
    page's new rows are ONE run of consecutive rows with consecutive
    destinations (as ``build_ragged_batch`` lays a sequence's rows)."""
    rows, group = fit(k.shape[0], k.shape[1], k.shape[2], block_size,
                      cache_k.dtype, v.shape[2])
    return _append(cache_k, cache_v, k, v, pages, layer, block_size, rows,
                   group, paged_attention.INTERPRET)


# jitted, as page_list is, so that the step programs of one token bucket
# (one for each bucket of block-table widths) trace it once between them
@functools.partial(jax.jit, static_argnames=("block_size", "rows", "group",
                                             "interpret"))
def _append(cache_k, cache_v, k, v, pages, layer, block_size, rows, group,
            interpret):
    t, nkv, d = k.shape
    dv = v.shape[2]
    bs = block_size
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = lambda w: pl.BlockSpec((rows, nkv * w), lambda g, *_: (g, 0))
    spread = lambda w: pltpu.VMEM((w // _LANES, nkv, rows + 2 * bs, _LANES),
                                  jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(pl.cdiv(t, rows),),
        in_specs=[block(d), block(dv), hbm, hbm],
        out_specs=[hbm, hbm],
        scratch_shapes=[
            spread(d), spread(dv),
            pltpu.VMEM((group, nkv, bs, d), cache_k.dtype),
            pltpu.VMEM((group, nkv, bs, dv), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, group)),
            pltpu.SemaphoreType.DMA((2, group)),
        ],
    )
    ends, *tables = pages
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
                   jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype)],
        # operands 8 and 9 (after the entries' five tables, the layer and
        # the rows): the pools, written in place
        input_output_aliases={8: 0, 9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kv_append",
    # rounded to the pool's dtype once, as the scatter did
    )(ends, jnp.asarray(layer, jnp.int32).reshape(1), *tables,
      k.astype(cache_k.dtype).reshape(t, nkv * d),
      v.astype(cache_v.dtype).reshape(t, nkv * dv), cache_k, cache_v)
