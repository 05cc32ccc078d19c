"""Repo-owned Pallas paged (block-table) attention for the inference v2
ragged step: prefill chunks, speculative verify runs and decode rows.

TPU replacement for the reference's ragged blocked-flash CUDA kernels
(``/root/reference/deepspeed/inference/v2/kernels/ragged_ops/`` — blocked
flash over a KV block table). Design of ``paged_qblock``, the kernel
behind :func:`paged_decode_attention` for a bf16/fp32 cache:

* **Query-blocked grid (T / QB,)**: one program serves ``QB`` consecutive
  rows of the step's flat token array, every KV head.  It cuts the block
  into RUNS of consecutive rows of one sequence (read from ``token_slot``,
  at run time, per block) and walks each run's live pages ONCE — from the
  page of the window's lower edge at the run's smallest position to the
  causal frontier of its largest — with all ``QB x group`` query rows of
  a KV head as the left operand ``[QB*group, d]``.  A 256-token prefill
  chunk therefore visits its pages once per 32 rows, not once per row; a
  decode row is a run of one and walks its own pages, no others.  (The
  row-per-program grid (T, nkv) this replaces took 150 ms a call on a
  251-row chunk at context 3,840, this one 0.66 ms: PERF.md, PR 27.)
* **The tile a walk multiplies is sized to its run**: a run of ONE row (a
  decode row, alone in a decode bucket or beside a chunk, or the piece a
  block boundary cuts off a longer run) is multiplied, soft-maxed and
  accumulated on a window of ``narrow_rows`` tile rows (16, or 32 for a
  group that does not divide 16) from a 16-row boundary, which holds its
  ``group`` query rows; a run of two rows or more takes the whole tile.
  The kernel decides a walk at a time, from the run's length at run time;
  same pages, same DMAs, same arithmetic on the live rows.  (With 16
  query heads to a KV head a one-row walk multiplied 256-512 rows for 16
  live and ran at a fifth of its bytes' time: PERF.md, PR 54.)
* **Masks are per row**: causal, context-length and sliding-window masks
  come from each row's own ``token_pos`` / ``token_ctx_len``, and rows
  outside the run being walked are masked out, so the kernel relies on no
  row order — ``build_ragged_batch``'s contiguous runs only make the
  shared walks long.
* **A compute step takes up to 512 keys**: that many whole pages, each
  one strided DMA of its rows of ALL KV heads (``pltpu.make_async_copy``
  out of the HBM-resident pool), land in one ``[nkv, keys, d]`` buffer,
  double-buffered against the previous step's compute, so a score tile
  ``[QB*group, keys]`` fills the lanes and the MXU instead of a 16-lane
  page tile, and a page costs one descriptor, not one per KV head.
* **Online softmax** state (m, l, acc) is per row and KV head, float32,
  in VMEM scratch; operands are the cache's dtype, scores float32.  Dead
  pages (beyond the run's causal frontier, or before its window) are
  never visited.
* **GQA-native**: a KV head's ``group`` query heads ride the row axis of
  its query tile — KV heads are never repeated, and every contraction is
  a plain rank-2 matmul (Mosaic-friendly; no in-kernel reshapes: XLA
  lays the queries out ``[nkv, T*group, d]`` before the call and back
  after it).
* No [T, C, nkv, d] gather is ever materialised in HBM (the XLA
  fallback's cost), and no per-token page table either: the kernel reads
  ``block_tables[token_slot[t]]`` from SMEM itself.

The int8-KV variant ``paged_decode_q8`` keeps the row-per-program grid
(T, nkv) and a page a step (no cell or deployment measures it yet).

Cache layout contract: k_pages/v_pages are one layer's pages ``[nkv, P, d]``
where P = number of pages × block_size rows (V rows may be of a width of
their own, ``[nkv, P, dv]``: scores contract over ``d``, the accumulator
and the output are ``dv`` wide), or EVERY layer's pool
``[L, nkv, P, .]`` with ``layer`` naming the one to read: the index is one
more scalar-prefetched operand and a page's DMA is ``pool[layer, :, rows]``,
so a layer loop carries the pool whole and no layer is ever sliced out of it
into a value of its own (as ``ssd_ragged`` takes its state).  The kernels
only read the pool.  ``pages[s, j]`` gives page ids (row-blocks of
``block_size``). Positions ``c = j*block_size + r`` are masked against the
token's causal position and its sequence's context length.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

INTERPRET = False

# Rows of the step's flat token array one program serves (``QB``); the
# host reads it for the ``blocked_rows`` count of the ``v2.schedule`` span.
QUERY_BLOCK = 32
# Keys one compute step of a walk takes (whole pages; at least one), as
# far as the K and V double buffers of all KV heads fit their VMEM share.
_STEP_KEYS = 512
_KV_BUFFER_BYTES = 4 * 1024 * 1024


_LANES = 128


def row_width(d: int) -> int:
    """Lanes a pool keeps for a row of ``d`` dims: ``d`` up to one
    128-lane tile, whole tiles past it (192 -> 256: the chip lays a
    ``[.., rows, 192]`` array out in 256 lanes whatever its shape says,
    and a page's DMA wants whole tiles; the pad lanes hold zeros, which
    add nothing to a score)."""
    return d if d <= _LANES else -(-d // _LANES) * _LANES


def supports(block_size: int, d: int, dv: int | None = None) -> bool:
    """Kernel applicability (``paged_qblock``, bf16 / f32 pools): page
    rows must be sublane-aligned, and K rows and V rows lane-aligned AS
    THE POOLS KEEP THEM — the page DMA slices ``[bs, row]`` out of the HBM
    pool, and Mosaic refuses a slice whose minor dim is not a multiple of
    the 128-lane tiling.  K rows of ``d`` dims and V rows of ``dv``
    (``None``: ``d``) may differ: a key width past one tile that is no
    multiple of 128 is TAKEN, kept in :func:`row_width` lanes (192 in
    256, 1.2 x the published bytes of a 192 / 128 token); a value width
    must be whole tiles as it is (it is the output's); a key narrower
    than a tile (head_dim 64 models) is refused and rides ``paged_xla``.
    A learned sink a head is taken.  The int8-KV kernel
    (``paged_decode_q8``) takes ONE width and no sink."""
    dv = d if dv is None else dv
    return (block_size >= 8 and block_size % 8 == 0 and d >= _LANES
            and dv % _LANES == 0)


def shared_walk_rows(run_lengths, query_block: int = QUERY_BLOCK) -> int:
    """Rows of a step that share a page walk with a neighbour: the step's
    rows are its sequences' runs laid end to end (``run_lengths``, in row
    order), a program cuts them at every multiple of ``query_block``, and
    a piece of two rows or more is walked once for all its rows."""
    shared = cursor = 0
    for n in run_lengths:
        end = cursor + n
        while cursor < end:
            piece = min(end, (cursor // query_block + 1) * query_block) \
                - cursor
            if piece > 1:
                shared += piece
            cursor += piece
    return shared


def narrow_rows(group: int, rows: int) -> int:
    """Tile rows a run of ONE row is multiplied on (0: the whole tile of
    ``rows``): its ``group`` query rows start up to ``16 - gcd(16, group)``
    rows past a 16-row boundary (16: the sublanes a packed bf16 tile
    holds, so a window from there is a whole-tile slice in either dtype),
    so they lie inside this many rows from it: 16 where ``group`` divides
    16, 32 for groups of 5 and 6."""
    reach = 16 - math.gcd(16, group) + group
    nr = -(-reach // 16) * 16
    return nr if rows % 16 == 0 and nr < rows else 0


def _kernel_qblock(tables_ref, slot_ref, pos_ref, clen_ref, layer_ref, q_ref,
                   rowpos_ref, rowclen_ref, *refs, bs, group, qb,
                   pages_per_step, sm_scale, window=None, sink=False):
    """Grid (T / qb,): one program, ``qb`` rows of the token array, every
    KV head; ``q_ref`` is ``[nkv, qb*group, d]`` and ``o_ref`` ``[nkv,
    qb*group, dv]``, row = token * group + head of the group; ``k_hbm``
    ``[L, nkv, P, d]`` and ``v_hbm`` ``[L, nkv, P, dv]`` are every
    layer's pool and ``layer_ref[0]`` the layer to read.  The rows are
    cut into runs of one sequence; each run is one double-buffered walk
    of ``pages_per_step`` pages a step, a page's rows of all KV heads in
    one strided DMA a pool.  With ``sink`` one more operand
    ``[nkv, qb*group, 1]`` comes before the pools: each row's head's
    learned logit, with which its online softmax STARTS (running max the
    logit, running sum ``exp(0)``, nothing accumulated): it takes
    probability and adds no value."""
    if sink:
        sink_ref, *refs = refs
    (k_hbm, v_hbm, o_ref, m_scr, l_scr, acc_scr, k_buf, v_buf, sem_k,
     sem_v) = refs
    base = pl.program_id(0) * qb
    layer = layer_ref[0]
    nkv, rows = q_ref.shape[:2]
    narrow = narrow_rows(group, rows)
    if sink:
        m_scr[...] = sink_ref[...]
        l_scr[...] = jnp.ones(l_scr.shape, jnp.float32)
    else:
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    row_pos = rowpos_ref[...]                            # [rows, 1]
    row_clen = rowclen_ref[...]

    def walk(r0, r1, seq, pmin, pmax, cmax):
        """Rows [r0, r1) of the block belong to table row ``seq``: visit
        its pages once, every row masked by its own position."""
        j_lo = jnp.int32(0)
        if window is not None:
            j_lo = jnp.maximum((pmin - (window - 1)) // bs, 0)
        # one past the causal frontier page; a run with no context (the
        # padded tail) walks nothing
        j_hi = jnp.where(cmax > 0, pmax // bs + 1, j_lo)
        n_steps = (j_hi - j_lo + pages_per_step - 1) // pages_per_step

        def page_copies(buf, p, page):
            dst = pl.dslice(pl.multiple_of(p * bs, bs), bs)
            src = pl.dslice(page * bs, bs)
            return (pltpu.make_async_copy(k_hbm.at[layer, :, src],
                                          k_buf.at[buf, :, dst],
                                          sem_k.at[buf]),
                    pltpu.make_async_copy(v_hbm.at[layer, :, src],
                                          v_buf.at[buf, :, dst],
                                          sem_v.at[buf]))

        def start_step(n, buf):
            def start_page(p, _):
                # past the frontier: fetch the last live page again (its
                # keys sit beyond every row's position, so they are
                # masked) — the buffer never holds uninitialised rows
                j = jnp.minimum(j_lo + n * pages_per_step + p, j_hi - 1)
                for copy in page_copies(buf, p, tables_ref[seq, j]):
                    copy.start()
                return 0

            lax.fori_loop(0, pages_per_step, start_page, 0)

        def wait_step(buf):
            def wait_page(p, _):
                # wait() only consumes (sem, dst-bytes) — the src slice
                # need not be the one the copy was started with
                for copy in page_copies(buf, p, 0):
                    copy.wait()
                return 0

            lax.fori_loop(0, pages_per_step, wait_page, 0)

        @pl.when(n_steps > 0)
        def _():
            start_step(0, 0)

        one_row = r1 - r0 == 1

        def step(n, buf, start=None):
            """One compute step on the whole tile, or on its ``narrow``
            rows from ``start``: rows outside the run, and rows with no
            live key here, keep their state as it was (``alpha`` 1, ``p``
            0)."""
            if start is None:
                sl, rws, rpos, rclen = slice(None), row, row_pos, row_clen
            else:
                sl = pl.ds(start, narrow)
                rws = start + lax.broadcasted_iota(jnp.int32, (narrow, 1), 0)
                rpos, rclen = rowpos_ref[sl], rowclen_ref[sl]
            c = (lax.broadcasted_iota(
                jnp.int32, (rws.shape[0], pages_per_step * bs), 1)
                 + (j_lo + n * pages_per_step) * bs)
            valid = ((rws >= r0 * group) & (rws < r1 * group)
                     & (c <= rpos) & (c < rclen))
            if window is not None:
                valid &= rpos - c < window

            def head(h, _):
                k = k_buf[buf, h]                        # [step_keys, d]
                v = v_buf[buf, h]
                s = jax.lax.dot_general(
                    q_ref[h, sl], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [rows, step_keys]
                s = jnp.where(valid, s * sm_scale, NEG_INF)
                m_prev = m_scr[h, sl]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # a row with no live key in this step (another run's row,
                # or one whose frontier lies before it) must add nothing:
                # without the select its exp(NEG_INF - NEG_INF) would be 1
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l_scr[h, sl] = l_scr[h, sl] * alpha + jnp.sum(
                    p, axis=1, keepdims=True)
                acc_scr[h, sl] = acc_scr[h, sl] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [rows, d]
                m_scr[h, sl] = m_new
                return 0

            lax.fori_loop(0, nkv, head, 0)

        def body(n, _):
            buf = lax.rem(n, 2)

            @pl.when(n + 1 < n_steps)
            def _():
                start_step(n + 1, 1 - buf)

            wait_step(buf)
            if not narrow:
                step(n, buf)
                return 0

            @pl.when(one_row)
            def _():
                # the run's ``group`` query rows lie inside ``narrow`` tile
                # rows from a 16-row boundary (a packed bf16 sublane tile)
                step(n, buf, pl.multiple_of(
                    jnp.minimum(r0 * group // 16 * 16, rows - narrow), 16))

            @pl.when(jnp.logical_not(one_row))
            def _():
                step(n, buf)

            return 0

        lax.fori_loop(0, n_steps, body, 0)

    big = jnp.int32(2 ** 30)

    def row_body(i, run):
        # one pass over the block's rows: a run ends where the next row
        # belongs to another sequence, and is walked there
        r0, pmin, pmax, cmax = run
        seq = slot_ref[base + i]
        pmin = jnp.minimum(pmin, pos_ref[base + i])
        pmax = jnp.maximum(pmax, pos_ref[base + i])
        cmax = jnp.maximum(cmax, clen_ref[base + i])
        last = (i == qb - 1) | (
            slot_ref[base + jnp.minimum(i + 1, qb - 1)] != seq)

        @pl.when(last)
        def _():
            walk(r0, i + 1, seq, pmin, pmax, cmax)

        return (jnp.where(last, i + 1, r0), jnp.where(last, big, pmin),
                jnp.where(last, -1, pmax), jnp.where(last, 0, cmax))

    lax.fori_loop(0, qb, row_body,
                  (jnp.int32(0), big, jnp.int32(-1), jnp.int32(0)))

    l = l_scr[...]
    o_ref[...] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _kernel_quant(pages_ref, pos_ref, clen_ref, layer_ref, q_ref, ksc_ref,
                  vsc_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem_k, sem_v, *,
                  bs, group, sm_scale, window=None):
    """Int8-KV row kernel, grid (T, nkv): ONE program per (token, KV head)
    walks that token's live pages, a page a step, in an in-kernel
    fori_loop with double-buffered manual DMA.  The page payloads, read out
    of every layer's pool ``[L, nkv, P, d]`` at ``layer_ref[0]``, are int8
    with one fp32 scale per (head, row).  Only the d-wide payload rides
    the manual double-buffered DMA (half the bytes of the bf16 cache — the
    decode bandwidth win); the per-head scales are small and arrive whole
    through an ordinary VMEM BlockSpec as ``[pages, bs]``, one sublane
    row per page.
    Scales fold into existing vectors: the k scale multiplies score
    COLUMNS after the q·k matmul, the v scale multiplies the softmax
    probabilities before p·v — no [bs, d] dequantized buffer ever
    materialises."""
    t = pl.program_id(0)
    h = pl.program_id(1)
    layer = layer_ref[0]
    pos = pos_ref[t]
    clen = clen_ref[t]
    j_lo = jnp.int32(0)
    if window is not None:
        j_lo = jnp.maximum((pos - (window - 1)) // bs, 0)
    j_hi = pos // bs + 1  # one past the causal frontier page

    def page_copy(j, slot):
        page = pages_ref[t, j]
        pltpu.make_async_copy(
            k_hbm.at[layer, h, pl.dslice(page * bs, bs)], k_buf.at[slot],
            sem_k.at[slot]).start()
        pltpu.make_async_copy(
            v_hbm.at[layer, h, pl.dslice(page * bs, bs)], v_buf.at[slot],
            sem_v.at[slot]).start()

    page_copy(j_lo, 0)
    q = q_ref[0, 0]                                      # [group, d]

    def body(j, carry):
        m_prev, l_prev, acc = carry
        slot = lax.rem(j - j_lo, 2)

        @pl.when(j + 1 < j_hi)
        def _():
            page_copy(j + 1, 1 - slot)

        # wait() only consumes (sem, dst-bytes) — the src slice need not
        # match the one the copy was started with, so a fixed slice
        # reconstructs an equivalent descriptor for the decrement
        pltpu.make_async_copy(k_hbm.at[layer, h, pl.dslice(0, bs)],
                              k_buf.at[slot], sem_k.at[slot]).wait()
        pltpu.make_async_copy(v_hbm.at[layer, h, pl.dslice(0, bs)],
                              v_buf.at[slot], sem_v.at[slot]).wait()
        page = pages_ref[t, j]
        ks = ksc_ref[0, pl.dslice(page, 1), :]           # [1, bs] f32
        vs = vsc_ref[0, pl.dslice(page, 1), :]
        k = k_buf[slot].astype(jnp.float32)              # int8 rows exact
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [group, bs]
        s = s * (sm_scale * ks)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        valid = (c <= pos) & (c < clen)
        if window is not None:
            valid &= pos - c < window
        s = jnp.where(valid, s, NEG_INF)

        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new)                           # [group, bs]
        l_new = l_prev * alpha + jnp.sum(e, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            e * vs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [group, d]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((group, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((group, 1), jnp.float32)
    a0 = jnp.zeros((group, q.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(j_lo, j_hi, body, (m0, l0, a0))
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)


def _decode_q8(q, k_pages, v_pages, pages, token_pos, token_ctx_len, k_scales,
               v_scales, layer, bs, sm_scale, window):
    """The int8-KV row kernel's call: ``pages`` is [T, NB], a table per
    token; the pools are every layer's, the scales ``[L, nkv, P]``."""
    t, nh, d = q.shape
    nkv, p_rows = k_pages.shape[1], k_pages.shape[2]
    group = nh // nkv
    # q reshaped to [T, nkv, group, d] outside: one KV head's query
    # group per block, full trailing dims (Mosaic block constraint)
    q_spec = pl.BlockSpec((1, 1, group, d), lambda t_, h, *refs: (t_, h, 0, 0))
    # whole per-head scales live in VMEM via the normal pipeline,
    # viewed [nkv, pages, bs] so the block's trailing dims are the
    # array's own (Mosaic block constraint) and a page's scales are
    # one dynamically indexed sublane row.  The view is a relayout on the
    # chip, so it is taken of the one layer's scales (1/d of its payload)
    # and never of the pool's
    n_pages = p_rows // bs
    sc_spec = pl.BlockSpec((1, n_pages, bs), lambda t_, h, *refs: (h, 0, 0))
    scales = tuple(
        lax.dynamic_index_in_dim(s, layer[0], 0, keepdims=False)
        .astype(jnp.float32).reshape(nkv, n_pages, bs)
        for s in (k_scales, v_scales))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t, nkv),
        # the page pools stay in HBM; the kernel DMAs live pages into
        # its double buffer itself
        in_specs=[q_spec, sc_spec, sc_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, bs, d), k_pages.dtype),   # k double buffer
            pltpu.VMEM((2, bs, d), v_pages.dtype),   # v double buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_quant, bs=bs, group=group,
                          sm_scale=sm_scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, nkv, group, d), q.dtype),
        interpret=INTERPRET,
        name="paged_decode_q8",
    )(pages.astype(jnp.int32), token_pos.astype(jnp.int32),
      token_ctx_len.astype(jnp.int32), layer, q.reshape(t, nkv, group, d),
      *scales, k_pages, v_pages)
    return out.reshape(t, nh, d)


@functools.partial(jax.jit, static_argnames=("block_size", "sm_scale",
                                             "window"))
def paged_decode_attention(q, k_pages, v_pages, pages, token_pos,
                           token_ctx_len, block_size: int, sm_scale: float,
                           window: int | None = None,
                           k_scales=None, v_scales=None, token_slot=None,
                           layer=None, sink=None):
    """q: [T, nh, d]; k_pages: [nkv, P, d] and v_pages: [nkv, P, dv], or
    every layer's pool [L, nkv, P, .] with ``layer`` (a traced scalar will
    do) naming the one to read; token_pos/token_ctx_len: [T]; ``pages``:
    page ids, [S, NB] block tables with ``token_slot`` [T] naming each
    token's table row, or [T, NB] a table per token without it;
    ``window``: Mistral sliding window (key visible iff qpos - kpos <
    window); ``sink`` [nh]: a learned logit a head that joins every row's
    softmax denominator and adds no value.  With
    ``k_scales``/``v_scales`` [nkv, P] (or [L, nkv, P]) the page payloads
    are int8 rows scaled per (head, row) — ref KV-block layout
    inference/v2/ragged/kv_cache.py:40: one width, no sink.  Returns
    [T, nh, dv]."""
    if layer is None:
        # one layer's pages are a pool of one layer (a free reshape)
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
        layer = 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    t, nh, d = q.shape
    nkv, dv = k_pages.shape[1], v_pages.shape[3]
    group = nh // nkv
    bs = block_size
    i32 = lambda a: a.astype(jnp.int32)
    if k_scales is not None:
        if sink is not None or dv != d:
            raise NotImplementedError(
                "paged_decode_q8 (the int8-KV kernel) takes K and V rows "
                "of ONE width and no sink in the softmax; a model with "
                "either keeps its pools in the compute dtype "
                "(paged_qblock)")
        if token_slot is not None:
            pages = pages[token_slot]
        return _decode_q8(q, k_pages, v_pages, pages, token_pos,
                          token_ctx_len, k_scales, v_scales, layer, bs,
                          sm_scale, window)

    slot = (jnp.arange(t, dtype=jnp.int32) if token_slot is None
            else i32(token_slot))
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    q4 = q.reshape(t, nkv, group, d)
    pos, clen = i32(token_pos), i32(token_ctx_len)
    if pad:
        # rows of no sequence (slot -1) with no context: walked by nobody
        q4 = jnp.pad(q4, ((0, pad), (0, 0), (0, 0), (0, 0)))
        slot = jnp.pad(slot, (0, pad), constant_values=-1)
        pos, clen = jnp.pad(pos, (0, pad)), jnp.pad(clen, (0, pad))
    tp = t + pad
    rows = qb * group
    step_keys = min(_STEP_KEYS, _KV_BUFFER_BYTES
                    // (2 * nkv * (d + dv) * k_pages.dtype.itemsize))
    pages_per_step = max(1, step_keys // bs)

    # per KV head a dense tile of its query groups, row = token * group +
    # head of the group: no kernel-side relayout, one XLA transpose each way
    q_spec = pl.BlockSpec((nkv, rows, d), lambda b, *refs: (0, b, 0))
    o_spec = pl.BlockSpec((nkv, rows, dv), lambda b, *refs: (0, b, 0))
    # each tile row's own position and context length, as columns
    col_spec = pl.BlockSpec((rows, 1), lambda b, *refs: (b, 0))
    col = lambda a: jnp.repeat(a, group)[:, None]
    sinks = ()
    if sink is not None:
        # every block's rows alike: row r of KV head h is query head
        # h * group + r % group
        sinks = (jnp.tile(sink.astype(jnp.float32).reshape(nkv, 1, group),
                          (1, qb, 1)).reshape(nkv, rows, 1),)
    sink_specs = [pl.BlockSpec((nkv, rows, 1), lambda b, *refs: (0, 0, 0))
                  for _ in sinks]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(tp // qb,),
        # the page pools stay in HBM; the kernel DMAs live pages into
        # its double buffer itself
        in_specs=[q_spec, col_spec, col_spec, *sink_specs,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((nkv, rows, 1), jnp.float32),  # running max
            pltpu.VMEM((nkv, rows, 1), jnp.float32),  # running sum
            pltpu.VMEM((nkv, rows, dv), jnp.float32),  # accumulator
            pltpu.VMEM((2, nkv, pages_per_step * bs, d), k_pages.dtype),
            pltpu.VMEM((2, nkv, pages_per_step * bs, dv), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_qblock, bs=bs, group=group, qb=qb,
                          pages_per_step=pages_per_step, sm_scale=sm_scale,
                          window=window, sink=bool(sinks)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nkv, tp * group, dv), q.dtype),
        interpret=INTERPRET,
        name="paged_qblock",
    )(i32(pages), slot, pos, clen, layer,
      q4.swapaxes(0, 1).reshape(nkv, tp * group, d), col(pos), col(clen),
      *sinks, k_pages, v_pages)
    out = out.reshape(nkv, tp, group, dv).swapaxes(0, 1)
    return out[:t].reshape(t, nh, dv)
