"""Repo-owned Pallas paged (block-table) attention for inference v2 decode.

TPU replacement for the reference's ragged blocked-flash CUDA kernels
(``/root/reference/deepspeed/inference/v2/kernels/ragged_ops/`` — blocked
flash over a KV block table). Design:

* **Grid (T, nkv)**: ONE program per (query token, KV head) walks that
  token's live pages in an in-kernel ``fori_loop`` with double-buffered
  manual DMA (``pltpu.make_async_copy``) out of the HBM-resident page
  pool — page ``tables[t, j+1]``'s copy is in flight while page
  ``tables[t, j]`` is being processed, the same prefetch loop the
  reference's CUDA kernel implements by hand.  (Putting the page walk on
  the grid instead costs T·nkv·NB invocations whose fixed per-step
  overhead dominated decode — measured r04: 7.3 → 2.3 ms/call at T=32,
  NB=128.)
* **Online softmax** state (m, l, acc) rides the loop carry; dead pages
  (beyond the causal frontier, or before the sliding window) are never
  visited at all.
* **GQA-native**: the q block for KV head ``h`` is its ``group`` query
  heads ``[group, d]``, matmul'd against the page block ``[bs, d]`` — KV
  heads are never repeated, and every contraction is a plain rank-2 matmul
  (Mosaic-friendly; no in-kernel reshapes).
* No [T, C, nkv, d] gather is ever materialised in HBM (the XLA fallback's
  cost, and the reason decode throughput was gather-bound in round 1).

Cache layout contract: k_pages/v_pages are ``[nkv, P, d]`` where P = number
of pages × block_size rows; ``pages[t, j]`` gives page ids (row-blocks of
``block_size``). Positions ``c = j*block_size + r`` are masked against the
token's causal position and its sequence's context length.
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


def supports(block_size: int, d: int) -> bool:
    """Kernel applicability: page rows must be sublane-aligned and the
    head dim lane-aligned — the page DMA slices ``[bs, d]`` out of the
    HBM pool, and Mosaic refuses a slice whose minor dim is not a
    multiple of the 128-lane tiling (head_dim 64 models ride
    ``paged_xla``)."""
    return block_size >= 8 and block_size % 8 == 0 and d % 128 == 0


def _kernel(pages_ref, pos_ref, clen_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem_k, sem_v, *, bs, group, sm_scale,
            window=None):
    """Grid (T, nkv): ONE program per (token, KV head) walks that token's
    live pages in an in-kernel fori_loop with double-buffered manual DMA
    from the HBM-resident page pool.  The previous design put the page
    walk on the grid — T·nkv·NB invocations whose fixed per-step cost
    (~0.6 µs on v5e) dominated decode (measured r04: 7.3 ms/call at
    T=32, NB=128 vs 0.35 ms for this form, with identical math)."""
    t = pl.program_id(0)
    h = pl.program_id(1)
    pos = pos_ref[t]
    clen = clen_ref[t]
    j_lo = jnp.int32(0)
    if window is not None:
        j_lo = jnp.maximum((pos - (window - 1)) // bs, 0)
    j_hi = pos // bs + 1  # one past the causal frontier page

    def page_copy(j, slot):
        page = pages_ref[t, j]
        ck = pltpu.make_async_copy(
            k_hbm.at[h, pl.dslice(page * bs, bs)], k_buf.at[slot],
            sem_k.at[slot])
        cv = pltpu.make_async_copy(
            v_hbm.at[h, pl.dslice(page * bs, bs)], v_buf.at[slot],
            sem_v.at[slot])
        ck.start()
        cv.start()

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
        pltpu.make_async_copy(k_hbm.at[h, pl.dslice(0, bs)],
                              k_buf.at[slot], sem_k.at[slot]).wait()
        pltpu.make_async_copy(v_hbm.at[h, pl.dslice(0, bs)],
                              v_buf.at[slot], sem_v.at[slot]).wait()
        k = k_buf[slot]                                  # [bs, d]
        v = v_buf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [group, bs]
        s = s * sm_scale
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        valid = (c <= pos) & (c < clen)
        if window is not None:
            valid &= pos - c < window
        s = jnp.where(valid, s, NEG_INF)

        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # [group, bs]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [group, d]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((group, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((group, 1), jnp.float32)
    a0 = jnp.zeros((group, q.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(j_lo, j_hi, body, (m0, l0, a0))
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)


def _kernel_quant(pages_ref, pos_ref, clen_ref, q_ref, ksc_ref, vsc_ref,
                  k_hbm, v_hbm, o_ref, k_buf, v_buf, sem_k, sem_v, *,
                  bs, group, sm_scale, window=None):
    """Int8-KV variant of :func:`_kernel`: the page payloads are int8 with
    one fp32 scale per (head, row).  Only the d-wide payload rides the
    manual double-buffered DMA (half the bytes of the bf16 cache — the
    decode bandwidth win); the per-head scales are small and arrive whole
    through an ordinary VMEM BlockSpec as ``[pages, bs]``, one sublane
    row per page.
    Scales fold into existing vectors: the k scale multiplies score
    COLUMNS after the q·k matmul, the v scale multiplies the softmax
    probabilities before p·v — no [bs, d] dequantized buffer ever
    materialises."""
    t = pl.program_id(0)
    h = pl.program_id(1)
    pos = pos_ref[t]
    clen = clen_ref[t]
    j_lo = jnp.int32(0)
    if window is not None:
        j_lo = jnp.maximum((pos - (window - 1)) // bs, 0)
    j_hi = pos // bs + 1

    def page_copy(j, slot):
        page = pages_ref[t, j]
        pltpu.make_async_copy(
            k_hbm.at[h, pl.dslice(page * bs, bs)], k_buf.at[slot],
            sem_k.at[slot]).start()
        pltpu.make_async_copy(
            v_hbm.at[h, pl.dslice(page * bs, bs)], v_buf.at[slot],
            sem_v.at[slot]).start()

    page_copy(j_lo, 0)
    q = q_ref[0, 0]                                      # [group, d]

    def body(j, carry):
        m_prev, l_prev, acc = carry
        slot = lax.rem(j - j_lo, 2)

        @pl.when(j + 1 < j_hi)
        def _():
            page_copy(j + 1, 1 - slot)

        pltpu.make_async_copy(k_hbm.at[h, pl.dslice(0, bs)],
                              k_buf.at[slot], sem_k.at[slot]).wait()
        pltpu.make_async_copy(v_hbm.at[h, pl.dslice(0, bs)],
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


@functools.partial(jax.jit, static_argnames=("block_size", "sm_scale",
                                             "window"))
def paged_decode_attention(q, k_pages, v_pages, pages, token_pos,
                           token_ctx_len, block_size: int, sm_scale: float,
                           window: int | None = None,
                           k_scales=None, v_scales=None):
    """q: [T, nh, d]; k_pages/v_pages: [nkv, P, d]; pages: [T, NB] page ids
    per token; token_pos/token_ctx_len: [T]; ``window``: Mistral sliding
    window (key visible iff qpos - kpos < window).  With
    ``k_scales``/``v_scales`` [nkv, P] the page payloads are int8 rows
    scaled per (head, row) — ref KV-block layout
    inference/v2/ragged/kv_cache.py:40.  Returns [T, nh, d]."""
    t, nh, d = q.shape
    nkv, p_rows = k_pages.shape[0], k_pages.shape[1]
    group = nh // nkv
    bs = block_size
    quant = k_scales is not None

    in_specs = [
        # q reshaped to [T, nkv, group, d] outside: one KV head's query
        # group per block, full trailing dims (Mosaic block constraint)
        pl.BlockSpec((1, 1, group, d), lambda t_, h, *refs: (t_, h, 0, 0)),
    ]
    extra = ()
    if quant:
        # whole per-head scales live in VMEM via the normal pipeline,
        # viewed [nkv, pages, bs] so the block's trailing dims are the
        # array's own (Mosaic block constraint) and a page's scales are
        # one dynamically indexed sublane row
        n_pages = p_rows // bs
        sc_spec = pl.BlockSpec((1, n_pages, bs),
                               lambda t_, h, *refs: (h, 0, 0))
        in_specs += [sc_spec, sc_spec]
        extra = tuple(s.astype(jnp.float32).reshape(nkv, n_pages, bs)
                      for s in (k_scales, v_scales))
    in_specs += [
        # the page pools stay in HBM; the kernel DMAs live pages into
        # its double buffer itself
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda t_, h, *refs: (t_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bs, d), k_pages.dtype),   # k double buffer
            pltpu.VMEM((2, bs, d), v_pages.dtype),   # v double buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kern = _kernel_quant if quant else _kernel
    out = pl.pallas_call(
        functools.partial(kern, bs=bs, group=group, sm_scale=sm_scale,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, nkv, group, d), q.dtype),
        interpret=INTERPRET,
        name="paged_decode_q8" if quant else "paged_decode",
    )(pages.astype(jnp.int32), token_pos.astype(jnp.int32),
      token_ctx_len.astype(jnp.int32), q.reshape(t, nkv, group, d),
      *extra, k_pages, v_pages)
    return out.reshape(t, nh, d)
