"""Ring attention: context parallelism by rotating K/V blocks over ICI.

The second half of the long-context story (the task the reference covers
with Ulysses all-to-all + FPDT chunking; ring attention is the
blockwise-rotation alternative of Liu et al. 2023): queries stay local to
their sequence shard while K/V blocks travel the "seq" mesh ring one
neighbour per hop (``lax.ppermute``), and a flash-style online softmax
accumulates each visiting block.  Communication per hop is O(S_local·d)
nearest-neighbour traffic that XLA overlaps with the block's attention
compute — and, unlike Ulysses, there is NO heads % sp divisibility
requirement, so it scales past the KV-head count (GQA models with 8 KV
heads on a 16-way context mesh).

Perf-grade inner block: on TPU (or under the Pallas interpreter) each
FORWARD hop is ONE fused flash pass — :func:`flash_carry_block` threads
the online softmax carry (m, l, acc) through the kernel, so no fp32
``[S_l, S_l]`` score block reaches HBM on the forward and causally-dead
tiles are skipped at the grid level.  The BACKWARD hops are fused the
same way: offset-aware dq/dkv flash kernels
(:func:`flash_ring_dq_block` / :func:`flash_ring_dkv_block`) reuse the
saved (o, lse) residuals, compute ``delta = sum(do·o)`` ONCE per shard,
and accumulate straight into HBM buffers aliased in place — backward
transient memory drops from score-shaped (four fp32 [S_l, S_l] blocks
per hop) to block-shaped ([blk, blk] VMEM tiles).  Off-TPU the same
math runs as XLA einsums (the CPU test mesh) behind the same
``_kernel_enabled()`` gate, so parity tests cover both paths.

Causal scheduling: with the default ``contiguous`` placement, hops whose
source block lies entirely in the masked future are skipped outright
(``lax.cond`` around the attend — no score FLOPs), but the ring is
bulk-synchronous per hop so the skip saves energy, not wall-clock (rank 0
idles while rank sp-1 works).  ``placement="striped"`` fixes the load
balance (Striped Attention, arXiv 2311.09431): shard r owns tokens
``r, r+sp, r+2sp, …``, so every hop is a ~half-masked block on every rank
— the flash kernel's tile skipping then halves causal compute uniformly.
Callers feed striped data (:func:`stripe_sequence` /
:func:`unstripe_sequence` are pure global reshapes; the engine applies
them host-side to ids/labels) and positions follow automatically.

Gradients are a hand-written second ring pass (``jax.custom_vjp``): the
forward saves (o, lse) per shard, the backward rotates K/V again and
accumulates dk/dv on buffers that TRAVEL WITH their block, delivered home
by one final ppermute.  Because the forward scan is never differentiated,
no per-hop carry residual ever crosses the shard_map partial-manual
boundary — which is what used to make the XLA SPMD partitioner report an
"involuntary full rematerialization" (a replicated [B, S_l, H] backward
residual) when ring composed with ZeRO-2 on a data×seq mesh.  The saved
(o, lse) are tagged ``checkpoint_name`` "flash_out"/"flash_lse", so the
engine's flash-aware remat policies keep them and the backward never
re-runs the forward ring (see runtime/engine.py's ring policy upgrade).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import (BATCH_AXES, SEQ_AXIS,
                                             get_topology)
from deepspeed_tpu.utils.jax_compat import (all_axes_manual,
                                            get_abstract_mesh, shard_map,
                                            with_unit_axes)
from deepspeed_tpu.utils.platform import on_tpu

_NEG = -1e30

PLACEMENTS = ("contiguous", "striped")


class _RingSpec(NamedTuple):
    """Static per-call config (hashable: rides custom_vjp nondiff)."""
    sp: int
    rep: int
    scale: float
    causal: bool
    window: Optional[int]
    placement: str
    use_flash: bool
    # hop/compute interleave depth (step_schedule.ring_interleave): 1 =
    # attend then rotate (serial issue order); 2 = issue the next hop's
    # ppermute BEFORE the current hop's attend, so the K/V transfer is
    # dataflow-independent of the hop's kernels and the compiler can
    # overlap the two.  Math identical either way (the attend always
    # consumes the un-rotated buffers).
    interleave: int = 1
    # ring wire dtype (comm_quantization.ring_rotation): "fp32" keeps
    # the raw word-packed rotation; "int8"/"fp8" move block-quantized
    # payloads + fp32 per-row scales on the wire (module comment above
    # _rotate_quantized).  int8 dequantizes inside the flash kernels'
    # epilogues on the fused path; fp8 always decodes via the XLA codec.
    wire: str = "fp32"


# ----------------------------------------------------------------------
# Placement helpers
# ----------------------------------------------------------------------
def ring_position_map(s: int, sp: int, placement: str = "contiguous"):
    """Global token position held by each slot of the seq-sharded array
    ([S] int32).  Under ``striped`` placement shard r's slot j holds token
    ``r + sp*j`` — feed the model positions from this map (RoPE/ALiBi stay
    exact) when its inputs went through :func:`stripe_sequence`."""
    if placement == "striped" and sp > 1:
        s_l = s // sp
        i = jnp.arange(s, dtype=jnp.int32)
        return (i // s_l) + sp * (i % s_l)
    return jnp.arange(s, dtype=jnp.int32)


def stripe_sequence(x, sp: int, axis: int = 1):
    """Reorder a GLOBAL sequence-axis array from natural token order to
    striped placement (shard r gets tokens r, r+sp, …).  Pure reshape +
    transpose — apply before sharding (host-side ids/labels, or globally
    before jit).  Works on numpy and jax arrays."""
    if sp <= 1:
        return x
    s = x.shape[axis]
    if s % sp:
        raise ValueError(f"sequence length {s} not divisible by sp={sp}")
    s_l = s // sp
    shape = x.shape
    y = x.reshape(shape[:axis] + (s_l, sp) + shape[axis + 1:])
    return y.swapaxes(axis, axis + 1).reshape(shape)


def unstripe_sequence(x, sp: int, axis: int = 1):
    """Inverse of :func:`stripe_sequence`."""
    if sp <= 1:
        return x
    s = x.shape[axis]
    if s % sp:
        raise ValueError(f"sequence length {s} not divisible by sp={sp}")
    s_l = s // sp
    shape = x.shape
    y = x.reshape(shape[:axis] + (sp, s_l) + shape[axis + 1:])
    return y.swapaxes(axis, axis + 1).reshape(shape)


def _block_positions(block_idx, s_l: int, sp: int, placement: str):
    """Traced [s_l] global positions of the block owned by ``block_idx``."""
    i = jnp.arange(s_l, dtype=jnp.int32)
    if placement == "striped":
        return block_idx + sp * i
    return block_idx * s_l + i


def _block_bounds(block_idx, s_l: int, sp: int, placement: str):
    """Traced (lo, hi) global position range of a block (strides > 0)."""
    if placement == "striped":
        return block_idx, block_idx + sp * (s_l - 1)
    return block_idx * s_l, block_idx * s_l + s_l - 1


def _hop_dead(idx, src, s_l: int, spec: _RingSpec):
    """Whether the (query block idx, key block src) hop contributes
    nothing: the source block is entirely in the causal future, or
    entirely older than the sliding window."""
    q_lo, q_hi = _block_bounds(idx, s_l, spec.sp, spec.placement)
    k_lo, k_hi = _block_bounds(src, s_l, spec.sp, spec.placement)
    dead = jnp.bool_(False)
    if spec.causal:
        dead |= k_lo > q_hi
    if spec.window is not None:
        dead |= q_lo - k_hi >= spec.window
    return dead


def _kernel_enabled() -> bool:
    """Run the Pallas carry kernel: whenever the flash module's INTERPRET
    flag is up (CPU parity tests), and on TPU where every mesh axis is
    manual at this point of the trace — with an axis left to GSPMD
    (tensor-sharded heads) the chip's compiler refuses the kernels and
    the XLA blocks run instead."""
    import importlib

    # the ops.pallas package re-exports the flash_mha *function* under the
    # same name as its submodule — resolve the module itself
    fm = importlib.import_module("deepspeed_tpu.ops.pallas.flash_mha")
    return fm.INTERPRET or (on_tpu() and all_axes_manual())


# ----------------------------------------------------------------------
# Hop rotation: every buffer that travels the ring in one hop moves in
# ONE collective launch.
# ----------------------------------------------------------------------
def _word_count(x) -> int:
    """Whole 32-bit words needed for ``x``'s bytes (ceil)."""
    return -(-int(np.prod(x.shape)) * x.dtype.itemsize // 4)


def _to_words(x):
    """Flatten to raw 32-bit words (bit-exact).  Sub-word dtypes pack 2
    (bf16/fp16) or 4 (int8) elements per word; an element count that
    does not fill the last word is ZERO-PADDED to the word boundary —
    ``_from_words`` slices the pad back off, so callers need no shape
    alignment (regression: odd head_dim / odd-length bf16 buffers used
    to fall back to per-buffer permutes)."""
    flat = x.reshape(-1)
    if x.dtype.itemsize == 4:
        return flat if x.dtype == jnp.uint32 \
            else lax.bitcast_convert_type(flat, jnp.uint32)
    per = 4 // x.dtype.itemsize
    if flat.size % per:
        flat = jnp.pad(flat, (0, per - flat.size % per))
    return lax.bitcast_convert_type(flat.reshape(-1, per), jnp.uint32)


def _from_words(w, shape, dtype):
    n = int(np.prod(shape))
    if dtype.itemsize == 4:
        out = w if dtype == jnp.uint32 \
            else lax.bitcast_convert_type(w, dtype)
        return out.reshape(shape)
    return lax.bitcast_convert_type(w, dtype).reshape(-1)[:n].reshape(shape)


def _rotate_together(perm, *xs):
    """Rotate every traveling buffer one ring neighbour in a SINGLE
    ``lax.ppermute``: flatten each to raw 32-bit words, concatenate,
    permute once, split and bitcast back.  ``lax.ppermute`` on a tuple
    tree-maps into one collective per leaf — on the backward ring that
    was four serialized collective-permute launches per hop for
    (kc, vc, dk_t, dv_t); one fused message keeps the ICI pipe busy with
    a single transfer the compiler can overlap with the hop's kernels.
    Byte-exact for 1/2/4-byte dtypes; tail elements that do not fill a
    word are pad-carried and sliced off on arrival (see _to_words)."""
    if any(x.dtype.itemsize not in (1, 2, 4)
           for x in xs):  # pragma: no cover - no such dtype travels today
        return tuple(lax.ppermute(x, SEQ_AXIS, perm) for x in xs)
    words = lax.ppermute(jnp.concatenate([_to_words(x) for x in xs]),
                         SEQ_AXIS, perm)
    out, i = [], 0
    for x in xs:
        n = _word_count(x)
        out.append(_from_words(words[i:i + n], x.shape, x.dtype))
        i += n
    return tuple(out)


# ----------------------------------------------------------------------
# Quantized wire (comm_quantization.ring_rotation): the traveling
# buffers move as int8 (or fp8-as-uint8) payloads + per-row fp32 block
# scales, the codec shared verbatim with comm/quantized.py
# (wire_encode_rows / wire_decode_rows — blocks are the trailing head
# dim).  K/V are encoded ONCE at ring entry and the payload+scales
# travel all sp-1 hops (a single quantization however long the ring);
# the traveling dk/dv grad accumulators change every hop, so they
# re-encode per hop.  Dequant on the consuming side happens inside the
# flash kernels' epilogues (flash_mha.wire_dequant_rows — new scale
# operands) on the fused path, or via wire_decode_rows on the XLA
# fallback, so the two codecs cannot drift.
# ----------------------------------------------------------------------
def _rotate_quantized(perm, payloads, scales):
    """One hop of the quantized wire: every payload flattens into ONE
    narrow message and every fp32 scale into another; a single
    ``lax.ppermute`` call moves the pair (one collective per dtype).
    Unlike :func:`_rotate_together` the payload is NOT word-packed — the
    wire dtype stays s8/u8 in the lowered HLO, so the static census
    (analysis/) sees the narrowed collective-permute it declares.
    Returns ``(payloads', scales')``."""
    pay = jnp.concatenate([p.reshape(-1) for p in payloads])
    sc = jnp.concatenate([s.reshape(-1) for s in scales])
    pay, sc = lax.ppermute((pay, sc), SEQ_AXIS, perm)
    outp, i = [], 0
    for p in payloads:
        n = int(np.prod(p.shape))
        outp.append(pay[i:i + n].reshape(p.shape))
        i += n
    outs, i = [], 0
    for s in scales:
        n = int(np.prod(s.shape))
        outs.append(sc[i:i + n].reshape(s.shape))
        i += n
    return tuple(outp), tuple(outs)


def _rotate_kv_grads_quant(perm, wire, kp, vp, ks, vs, dk, dv):
    """Backward-hop rotation on the quantized wire: the K/V payloads and
    scales pass through encoded, the fp32 traveling grads encode for the
    wire and decode on arrival — all four payloads in one message, all
    four scale vectors in another, one ``ppermute`` call."""
    from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                              wire_encode_rows)

    dkp, dks = wire_encode_rows(dk, wire)
    dvp, dvs = wire_encode_rows(dv, wire)
    (kp, vp, dkp, dvp), (ks, vs, dks, dvs) = _rotate_quantized(
        perm, (kp, vp, dkp, dvp), (ks, vs, dks, dvs))
    return (kp, vp, ks, vs, wire_decode_rows(dkp, dks, wire),
            wire_decode_rows(dvp, dvs, wire))


def _lane128(s):
    """Lane-replicate a compact per-row scale ``[..., 1]`` to the
    128-lane layout the flash kernels read (the lse/delta convention)."""
    return jnp.broadcast_to(s, s.shape[:-1] + (128,))


def _rotate_grads_quant(perm, wire, dk, dv):
    """Grads-only quantized rotation (the interleave-2 late half and the
    final delivery hop)."""
    from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                              wire_encode_rows)

    dkp, dks = wire_encode_rows(dk, wire)
    dvp, dvs = wire_encode_rows(dv, wire)
    (dkp, dvp), (dks, dvs) = _rotate_quantized(perm, (dkp, dvp),
                                               (dks, dvs))
    return (wire_decode_rows(dkp, dks, wire),
            wire_decode_rows(dvp, dvs, wire))


# ----------------------------------------------------------------------
# Local (per-shard) forward: XLA einsum path and Pallas flash path.
# Both return (o [b, s_l, nh, d], lse [b, nkv, rep, s_l] fp32).
# ----------------------------------------------------------------------
def _ring_fwd_xla(ql, kl, vl, spec: _RingSpec):
    b, s_l, nh, d = ql.shape
    nkv = kl.shape[2]
    rep = spec.rep
    # Only masked variants need the shard's ring position; dense
    # bidirectional hops never touch axis_index (whose partition-id
    # lowering old SPMD partitioners reject when it ends up dead code).
    masked = spec.causal or spec.window is not None
    idx = lax.axis_index(SEQ_AXIS) if masked else jnp.int32(0)
    # grouped-head layout: K/V stay at nkv heads END TO END — they travel
    # the ring UNREPEATED and feed the einsums unexpanded (per-hop ICI
    # traffic and per-hop HBM are both O(S_l·nkv·d))
    q5 = ql.astype(jnp.float32).reshape(b, s_l, nkv, rep, d)
    q_pos = _block_positions(idx, s_l, spec.sp, spec.placement)
    perm = [(i, (i + 1) % spec.sp) for i in range(spec.sp)]

    def attend(m, l, acc, kc, vc, src):
        k_pos = _block_positions(src, s_l, spec.sp, spec.placement)
        s = jnp.einsum("bqcgd,bscd->bcgqs", q5,
                       kc.astype(jnp.float32)) * spec.scale
        valid = jnp.ones((s_l, s_l), bool)
        if spec.causal:
            valid = q_pos[:, None] >= k_pos[None, :]
        if spec.window is not None:
            valid &= (q_pos[:, None] - k_pos[None, :]) < spec.window
        vm = valid[None, None, None]
        s = jnp.where(vm, s, _NEG)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # exp(NEG - NEG) would be 1 on fully-masked rows — zero the masked
        # probabilities explicitly
        p = jnp.where(vm, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bcgqs,bscd->bcgqd", p, vc.astype(jnp.float32))
        return m_new, l, acc

    def maybe_attend(m, l, acc, kc, vc, src):
        if not masked:
            return attend(m, l, acc, kc, vc, src)
        return lax.cond(_hop_dead(idx, src, s_l, spec),
                        lambda: (m, l, acc),
                        lambda: attend(m, l, acc, kc, vc, src))

    m0 = jnp.full((b, nkv, rep, s_l, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((b, nkv, rep, s_l, 1), jnp.float32)
    a0 = jnp.zeros((b, nkv, rep, s_l, d), jnp.float32)

    quant = spec.wire != "fp32"
    if quant:
        from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                                  wire_encode_rows)

        # hop 0 is the shard's OWN block: it never touches the wire, so
        # it attends EXACTLY (never causally dead either — the diagonal
        # is always live); only the traveling copy quantizes.  Encoding
        # happens ONCE here: payload + per-row scales travel all sp-1
        # hops, one quantization however long the ring.
        m, l, acc = attend(m0, l0, a0, kl, vl, idx)
        kp, ks = wire_encode_rows(kl, spec.wire)
        vp, vs = wire_encode_rows(vl, spec.wire)

        def maybe_attend_q(m, l, acc, kp, ks, vp, vs, src):
            def live():
                kf = wire_decode_rows(kp, ks, spec.wire)
                vf = wire_decode_rows(vp, vs, spec.wire)
                return attend(m, l, acc, kf, vf, src)

            if not masked:
                return live()
            return lax.cond(_hop_dead(idx, src, s_l, spec),
                            lambda: (m, l, acc), live)

        # first rotation peeled out of the scan (the scan body attends
        # then rotates, same shape as the fp32-wire loop)
        (kp, vp), (ks, vs) = _rotate_quantized(perm, (kp, vp), (ks, vs))

        def hop(carry, t):
            m, l, acc, kp, vp, ks, vs = carry
            src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
            if spec.interleave > 1:
                (nkp, nvp), (nks, nvs) = _rotate_quantized(
                    perm, (kp, vp), (ks, vs))
                m, l, acc = maybe_attend_q(m, l, acc, kp, ks, vp, vs, src)
                return (m, l, acc, nkp, nvp, nks, nvs), None
            m, l, acc = maybe_attend_q(m, l, acc, kp, ks, vp, vs, src)
            (kp, vp), (ks, vs) = _rotate_quantized(perm, (kp, vp),
                                                   (ks, vs))
            return (m, l, acc, kp, vp, ks, vs), None

        (m, l, acc, kp, vp, ks, vs), _ = lax.scan(
            hop, (m, l, acc, kp, vp, ks, vs), jnp.arange(spec.sp - 2))
        src_last = lax.rem(idx + 1, spec.sp)
        m, l, acc = maybe_attend_q(m, l, acc, kp, ks, vp, vs, src_last)
    else:
        # hop 0 = the shard's own block: attended first (it is never
        # causally dead), with the first rotation peeled out of the scan
        # — the same skeleton as the quantized branch, so the static
        # collective census counts both wires with identical op
        # multiplicity (analysis/; the scan body still holds sp-2
        # attend-then-rotate hops and the LAST block attends without the
        # dead ring rotation XLA cannot eliminate)
        m, l, acc = attend(m0, l0, a0, kl, vl, idx)
        kc, vc = _rotate_together(perm, kl, vl)

        def hop(carry, t):
            m, l, acc, kc, vc = carry
            src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
            if spec.interleave > 1:
                # rotate-ahead (interleave 2): the permute consumes only
                # the incoming buffers, so issuing it before the attend
                # makes transfer and compute dataflow-independent — the
                # scheduler is free to run the hop's kernels under the
                # K/V transfer
                nkc, nvc = _rotate_together(perm, kc, vc)
                m, l, acc = maybe_attend(m, l, acc, kc, vc, src)
                return (m, l, acc, nkc, nvc), None
            m, l, acc = maybe_attend(m, l, acc, kc, vc, src)
            kc, vc = _rotate_together(perm, kc, vc)
            return (m, l, acc, kc, vc), None

        (m, l, acc, kc, vc), _ = lax.scan(
            hop, (m, l, acc, kc, vc), jnp.arange(spec.sp - 2))
        src_last = lax.rem(idx + 1, spec.sp)
        m, l, acc = maybe_attend(m, l, acc, kc, vc, src_last)
    out = acc / jnp.maximum(l, 1e-20)            # [b, nkv, rep, q, d]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s_l, nh, d)
    lse = (m + jnp.log(jnp.maximum(l, 1e-20)))[..., 0]  # [b, nkv, rep, q]
    return out.astype(ql.dtype), lse


def _ring_fwd_flash(ql, kl, vl, spec: _RingSpec):
    """Same contract as :func:`_ring_fwd_xla` with the per-hop attend
    fused into one Pallas pass (flash_carry_block): the carry (m, l, acc)
    lives in HBM between hops, aliased in place, and dead tiles cost
    neither VPU masking nor MXU FLOPs."""
    from deepspeed_tpu.ops.pallas.flash_mha import (flash_carry_block,
                                                    ring_carry_pad)

    b, s_l, nh, d = ql.shape
    nkv = kl.shape[2]
    masked = spec.causal or spec.window is not None
    idx = lax.axis_index(SEQ_AXIS) if masked else jnp.int32(0)
    stride = spec.sp if spec.placement == "striped" else 1
    s_pad = ring_carry_pad(s_l)

    def to_kernel(x):  # [b, s, h, d] -> [b, h, s_pad, d]
        x = x.swapaxes(1, 2)
        if s_pad != s_l:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s_l), (0, 0)))
        return x

    qk, kk, vk = to_kernel(ql), to_kernel(kl), to_kernel(vl)
    q_off = (idx if spec.placement == "striped"
             else idx * s_l).astype(jnp.int32)
    perm = [(i, (i + 1) % spec.sp) for i in range(spec.sp)]

    def attend(m, l, acc, kc, vc, src, ks=None, vs=None):
        k_off = (src if spec.placement == "striped"
                 else src * s_l).astype(jnp.int32)
        kw = dict(q_stride=stride, k_stride=stride, s_real=s_l,
                  sm_scale=spec.scale, causal=spec.causal,
                  window=spec.window)
        if ks is not None:
            # quantized wire, fused dequant: the int8 payload feeds the
            # kernel directly with its per-row scales lane-replicated —
            # no fp32 K/V copy ever exists in HBM
            kw.update(k_scale=_lane128(ks), v_scale=_lane128(vs))
        return flash_carry_block(qk, kc, vc, m, l, acc, q_off, k_off,
                                 **kw)

    def maybe_attend(m, l, acc, kc, vc, src):
        if not masked:
            return attend(m, l, acc, kc, vc, src)
        return lax.cond(_hop_dead(idx, src, s_l, spec),
                        lambda: (m, l, acc),
                        lambda: attend(m, l, acc, kc, vc, src))

    m0 = jnp.full((b, nh, s_pad, 128), _NEG, jnp.float32)
    l0 = jnp.zeros((b, nh, s_pad, 128), jnp.float32)
    a0 = jnp.zeros((b, nh, s_pad, d), jnp.float32)

    quant = spec.wire != "fp32"
    if quant:
        from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                                  wire_encode_rows)

        # hop 0 = the shard's own block: exact attend (it never touches
        # the wire, and the diagonal is never dead); encode once for the
        # traveling copy (pad rows quantize to exact zeros)
        m, l, acc = attend(m0, l0, a0, kk, vk, idx)
        kp, ks = wire_encode_rows(kk, spec.wire)
        vp, vs = wire_encode_rows(vk, spec.wire)
        kernel_dequant = spec.wire == "int8"

        def maybe_attend_q(m, l, acc, kp, ks, vp, vs, src):
            def live():
                if kernel_dequant:
                    return attend(m, l, acc, kp, vp, src, ks=ks, vs=vs)
                # fp8 wire: the kernel has no fp8 lane — decode via the
                # XLA codec and run the plain kernel on the values
                kf = wire_decode_rows(kp, ks, spec.wire).astype(qk.dtype)
                vf = wire_decode_rows(vp, vs, spec.wire).astype(qk.dtype)
                return attend(m, l, acc, kf, vf, src)

            if not masked:
                return live()
            return lax.cond(_hop_dead(idx, src, s_l, spec),
                            lambda: (m, l, acc), live)

        # first rotation peeled out of the scan (the scan body attends
        # then rotates, same shape as the fp32-wire loop)
        (kp, vp), (ks, vs) = _rotate_quantized(perm, (kp, vp), (ks, vs))

        def hop(carry, t):
            m, l, acc, kp, vp, ks, vs = carry
            src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
            if spec.interleave > 1:
                (nkp, nvp), (nks, nvs) = _rotate_quantized(
                    perm, (kp, vp), (ks, vs))
                m, l, acc = maybe_attend_q(m, l, acc, kp, ks, vp, vs, src)
                return (m, l, acc, nkp, nvp, nks, nvs), None
            m, l, acc = maybe_attend_q(m, l, acc, kp, ks, vp, vs, src)
            (kp, vp), (ks, vs) = _rotate_quantized(perm, (kp, vp),
                                                   (ks, vs))
            return (m, l, acc, kp, vp, ks, vs), None

        (m, l, acc, kp, vp, ks, vs), _ = lax.scan(
            hop, (m, l, acc, kp, vp, ks, vs), jnp.arange(spec.sp - 2))
        src_last = lax.rem(idx + 1, spec.sp)
        m, l, acc = maybe_attend_q(m, l, acc, kp, ks, vp, vs, src_last)
    else:
        # hop 0 = own block, first rotation peeled — same skeleton as
        # the quantized branch (census op-multiplicity symmetry; see
        # _ring_fwd_xla)
        m, l, acc = attend(m0, l0, a0, kk, vk, idx)
        kc, vc = _rotate_together(perm, kk, vk)

        def hop(carry, t):
            m, l, acc, kc, vc = carry
            src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
            if spec.interleave > 1:
                # rotate-ahead (interleave 2): the permute consumes only
                # the incoming buffers, so issuing it before the attend
                # makes transfer and compute dataflow-independent — the
                # scheduler is free to run the hop's kernels under the
                # K/V transfer
                nkc, nvc = _rotate_together(perm, kc, vc)
                m, l, acc = maybe_attend(m, l, acc, kc, vc, src)
                return (m, l, acc, nkc, nvc), None
            m, l, acc = maybe_attend(m, l, acc, kc, vc, src)
            kc, vc = _rotate_together(perm, kc, vc)
            return (m, l, acc, kc, vc), None

        (m, l, acc, kc, vc), _ = lax.scan(
            hop, (m, l, acc, kc, vc), jnp.arange(spec.sp - 2))
        src_last = lax.rem(idx + 1, spec.sp)
        m, l, acc = maybe_attend(m, l, acc, kc, vc, src_last)

    m1 = m[:, :, :s_l, 0]                                # [b, nh, s_l]
    l1 = l[:, :, :s_l, 0]
    out = acc[:, :, :s_l] / jnp.maximum(l1, 1e-20)[..., None]
    out = out.swapaxes(1, 2).astype(ql.dtype)            # [b, s_l, nh, d]
    lse = m1 + jnp.log(jnp.maximum(l1, 1e-20))           # [b, nh, s_l]
    lse = lse.reshape(b, nkv, spec.rep, s_l)
    return out, lse


# ----------------------------------------------------------------------
# custom_vjp: forward ring + hand-written backward ring
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ring_local(ql, kl, vl, spec: _RingSpec):
    o, _ = (_ring_fwd_flash if spec.use_flash else _ring_fwd_xla)(
        ql, kl, vl, spec)
    return checkpoint_name(o, "flash_out")


def _ring_fwd_rule(ql, kl, vl, spec: _RingSpec):
    o, lse = (_ring_fwd_flash if spec.use_flash else _ring_fwd_xla)(
        ql, kl, vl, spec)
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (ql, kl, vl, o, lse)


def _ring_bwd_rule(spec: _RingSpec, res, do):
    """Flash-style ring backward: with the forward's (o, lse) saved, each
    hop recomputes only its own p = exp(s - lse) block and accumulates
    dq locally while dk/dv TRAVEL WITH their K/V block; one final
    ppermute delivers them to their owner shard.  Dead hops (fully-masked
    source blocks) are skipped like the forward, and every hop moves all
    four traveling buffers (kc, vc, dk_t, dv_t) in ONE stacked permute
    (:func:`_rotate_together`).

    On TPU / under the Pallas interpreter (``spec.use_flash``, the same
    gate as the forward) each hop's grads are TWO fused flash passes —
    offset-aware dq and dkv kernels accumulating in place — so no
    score-shaped fp32 transient reaches HBM.  Off-TPU the grads are XLA
    einsums (the CPU parity fallback), which do materialize the four
    fp32 [S_l, S_l] blocks per hop."""
    if spec.use_flash:
        return _ring_bwd_flash(spec, res, do)
    return _ring_bwd_xla(spec, res, do)


def _ring_bwd_xla(spec: _RingSpec, res, do):
    """XLA einsum backward hop (CPU/parity fallback): score-shaped fp32
    transients (s/p/dp/ds, ~4·s_l²·nkv·rep·4 B per hop)."""
    ql, kl, vl, o, lse = res
    masked = spec.causal or spec.window is not None
    idx = lax.axis_index(SEQ_AXIS) if masked else jnp.int32(0)
    b, s_l, nh, d = ql.shape
    nkv = kl.shape[2]
    rep = spec.rep
    q5 = ql.astype(jnp.float32).reshape(b, s_l, nkv, rep, d)
    do5 = do.astype(jnp.float32).reshape(b, s_l, nkv, rep, d)
    o5 = o.astype(jnp.float32).reshape(b, s_l, nkv, rep, d)
    from deepspeed_tpu.ops.pallas.flash_mha import attn_delta

    # delta = sum(do * o) per query row — [b, nkv, rep, s_l, 1]
    delta = attn_delta(o5, do5).transpose(0, 2, 3, 1)[..., None]
    lse_ = lse[..., None]                            # [b, nkv, rep, s_l, 1]
    q_pos = _block_positions(idx, s_l, spec.sp, spec.placement)
    perm = [(i, (i + 1) % spec.sp) for i in range(spec.sp)]

    def hop_grads(kc, vc, src):
        k_pos = _block_positions(src, s_l, spec.sp, spec.placement)
        kf = kc.astype(jnp.float32)
        vf = vc.astype(jnp.float32)
        s = jnp.einsum("bqcgd,bscd->bcgqs", q5, kf) * spec.scale
        valid = jnp.ones((s_l, s_l), bool)
        if spec.causal:
            valid = q_pos[:, None] >= k_pos[None, :]
        if spec.window is not None:
            valid &= (q_pos[:, None] - k_pos[None, :]) < spec.window
        vm = valid[None, None, None]
        p = jnp.where(vm, jnp.exp(s - lse_), 0.0)    # [b, c, g, q, s]
        dv_c = jnp.einsum("bcgqs,bqcgd->bscd", p, do5)
        dp = jnp.einsum("bqcgd,bscd->bcgqs", do5, vf)
        ds = p * (dp - delta) * spec.scale
        dq_c = jnp.einsum("bcgqs,bscd->bqcgd", ds, kf)
        dk_c = jnp.einsum("bcgqs,bqcgd->bscd", ds, q5)
        return dq_c, dk_c, dv_c

    def maybe_grads(kc, vc, src, zq, zk, zv):
        if not masked:
            return hop_grads(kc, vc, src)
        return lax.cond(_hop_dead(idx, src, s_l, spec),
                        lambda: (zq, zk, zv),
                        lambda: hop_grads(kc, vc, src))

    zq = jnp.zeros((b, s_l, nkv, rep, d), jnp.float32)
    zk = jnp.zeros((b, s_l, nkv, d), jnp.float32)
    # distinct zero block for dv: shape-identical to zk today, but dk/dv
    # layouts must be free to diverge without silently wrong grads
    zv = jnp.zeros((b, s_l, nkv, d), jnp.float32)

    quant = spec.wire != "fp32"
    if quant:
        from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                                  wire_encode_rows)

        def maybe_grads_q(kp, ks, vp, vs, src):
            def live():
                return hop_grads(wire_decode_rows(kp, ks, spec.wire),
                                 wire_decode_rows(vp, vs, spec.wire), src)

            if not masked:
                return live()
            return lax.cond(_hop_dead(idx, src, s_l, spec),
                            lambda: (zq, zk, zv), live)

        # own-block grads are exact (hop 0 never touches the wire and
        # the diagonal is never dead); encode once for the traveling copy
        dq, dk_t, dv_t = hop_grads(kl, vl, idx)
        kp, ks = wire_encode_rows(kl, spec.wire)
        vp, vs = wire_encode_rows(vl, spec.wire)
        # first rotation peeled out of the scan; K/V payloads and the
        # freshly-accumulated traveling grads move together
        kp, vp, ks, vs, dk_t, dv_t = _rotate_kv_grads_quant(
            perm, spec.wire, kp, vp, ks, vs, dk_t, dv_t)

        def hop(carry, t):
            dq, dk_t, dv_t, kp, vp, ks, vs = carry
            src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
            if spec.interleave > 1:
                (nkp, nvp), (nks, nvs) = _rotate_quantized(
                    perm, (kp, vp), (ks, vs))
                dq_c, dk_c, dv_c = maybe_grads_q(kp, ks, vp, vs, src)
                dk_t, dv_t = _rotate_grads_quant(perm, spec.wire,
                                                 dk_t + dk_c, dv_t + dv_c)
                return (dq + dq_c, dk_t, dv_t, nkp, nvp, nks, nvs), None
            dq_c, dk_c, dv_c = maybe_grads_q(kp, ks, vp, vs, src)
            kp, vp, ks, vs, dk_t, dv_t = _rotate_kv_grads_quant(
                perm, spec.wire, kp, vp, ks, vs, dk_t + dk_c, dv_t + dv_c)
            return (dq + dq_c, dk_t, dv_t, kp, vp, ks, vs), None

        (dq, dk_t, dv_t, kp, vp, ks, vs), _ = lax.scan(
            hop, (dq, dk_t, dv_t, kp, vp, ks, vs),
            jnp.arange(spec.sp - 2))
        src_last = lax.rem(idx + 1, spec.sp)
        dq_c, dk_c, dv_c = maybe_grads_q(kp, ks, vp, vs, src_last)
        dq = dq + dq_c
        # delivery hop: the traveling grads quantize one last time
        dk_t, dv_t = _rotate_grads_quant(perm, spec.wire,
                                         dk_t + dk_c, dv_t + dv_c)
        return (dq.reshape(b, s_l, nh, d).astype(ql.dtype),
                dk_t.astype(kl.dtype), dv_t.astype(vl.dtype))

    # hop 0 = own block, first rotation peeled — same skeleton as the
    # quantized branch (census op-multiplicity symmetry)
    if spec.interleave > 1:
        # rotate-ahead: K/V depart before even the own-block grads
        nkc, nvc = _rotate_together(perm, kl, vl)
        dq, dk_t, dv_t = hop_grads(kl, vl, idx)
        dk_t, dv_t = _rotate_together(perm, dk_t, dv_t)
        kc, vc = nkc, nvc
    else:
        dq, dk_t, dv_t = hop_grads(kl, vl, idx)
        # K/V and their accumulated grads rotate together, in one launch
        kc, vc, dk_t, dv_t = _rotate_together(perm, kl, vl, dk_t, dv_t)

    def hop(carry, t):
        dq, dk_t, dv_t, kc, vc = carry
        src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
        if spec.interleave > 1:
            # rotate-ahead: K/V depart before the hop's grads are
            # computed (overlapping the grad einsums); the traveling
            # grads must wait for their accumulation, so the single
            # fused 4-buffer permute splits into two 2-buffer permutes —
            # the interleave trades a second launch for an earlier K/V
            # transfer
            nkc, nvc = _rotate_together(perm, kc, vc)
            dq_c, dk_c, dv_c = maybe_grads(kc, vc, src, zq, zk, zv)
            dk_t, dv_t = _rotate_together(perm, dk_t + dk_c, dv_t + dv_c)
            return (dq + dq_c, dk_t, dv_t, nkc, nvc), None
        dq_c, dk_c, dv_c = maybe_grads(kc, vc, src, zq, zk, zv)
        dq = dq + dq_c
        dk_t = dk_t + dk_c
        dv_t = dv_t + dv_c
        # K/V and their accumulated grads rotate together, in one launch
        kc, vc, dk_t, dv_t = _rotate_together(perm, kc, vc, dk_t, dv_t)
        return (dq, dk_t, dv_t, kc, vc), None

    (dq, dk_t, dv_t, kc, vc), _ = lax.scan(
        hop, (dq, dk_t, dv_t, kc, vc), jnp.arange(spec.sp - 2))
    src_last = lax.rem(idx + 1, spec.sp)
    dq_c, dk_c, dv_c = maybe_grads(kc, vc, src_last, zq, zk, zv)
    dq = dq + dq_c
    # the traveling grads sit one rank behind their owner — deliver home
    dk_t, dv_t = _rotate_together(perm, dk_t + dk_c, dv_t + dv_c)
    return (dq.reshape(b, s_l, nh, d).astype(ql.dtype),
            dk_t.astype(kl.dtype), dv_t.astype(vl.dtype))


def _ring_bwd_flash(spec: _RingSpec, res, do):
    """Fused backward hop: offset-aware dq/dkv flash kernels
    (flash_ring_dq_block / flash_ring_dkv_block) reuse the saved
    (o, lse), consume ``delta = sum(do·o)`` computed ONCE per shard, and
    accumulate into fp32 HBM buffers aliased in place — per-hop
    transients are [blk, blk] VMEM tiles, never an [S_l, S_l] score
    block.  Dead tiles inside a live hop are skipped at the kernel grid
    level from the same traced offsets the forward carry kernel uses."""
    from deepspeed_tpu.ops.pallas.flash_mha import (bwd_lane_residuals,
                                                    flash_ring_dq_block,
                                                    flash_ring_dkv_block,
                                                    ring_carry_pad)

    ql, kl, vl, o, lse = res
    b, s_l, nh, d = ql.shape
    nkv = kl.shape[2]
    masked = spec.causal or spec.window is not None
    idx = lax.axis_index(SEQ_AXIS) if masked else jnp.int32(0)
    stride = spec.sp if spec.placement == "striped" else 1
    s_pad = ring_carry_pad(s_l)

    def to_kernel(x):  # [b, s, h, d] -> [b, h, s_pad, d]
        x = x.swapaxes(1, 2)
        if s_pad != s_l:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s_l), (0, 0)))
        return x

    qk, kk, vk, dok = (to_kernel(x) for x in (ql, kl, vl, do))
    # residual prep shared with the local flash backward (one helper so
    # the two paths can't drift): lane-replicated lse + per-shard delta
    lsep, deltap = bwd_lane_residuals(
        o.swapaxes(1, 2), do.swapaxes(1, 2), lse.reshape(b, nh, s_l),
        s_pad)
    q_off = (idx if spec.placement == "striped"
             else idx * s_l).astype(jnp.int32)
    perm = [(i, (i + 1) % spec.sp) for i in range(spec.sp)]

    def hop_grads(dq, dk_t, dv_t, kc, vc, src, ks=None, vs=None):
        k_off = (src if spec.placement == "striped"
                 else src * s_l).astype(jnp.int32)
        kw = dict(q_stride=stride, k_stride=stride, s_real=s_l,
                  sm_scale=spec.scale, causal=spec.causal,
                  window=spec.window)
        if ks is not None:
            # quantized wire, fused dequant (see _ring_fwd_flash.attend)
            kw.update(k_scale=_lane128(ks), v_scale=_lane128(vs))
        dq = flash_ring_dq_block(qk, kc, vc, dok, lsep, deltap, dq,
                                 q_off, k_off, **kw)
        dk_t, dv_t = flash_ring_dkv_block(qk, kc, vc, dok, lsep, deltap,
                                          dk_t, dv_t, q_off, k_off, **kw)
        return dq, dk_t, dv_t

    def maybe_grads(dq, dk_t, dv_t, kc, vc, src):
        if not masked:
            return hop_grads(dq, dk_t, dv_t, kc, vc, src)
        return lax.cond(_hop_dead(idx, src, s_l, spec),
                        lambda: (dq, dk_t, dv_t),
                        lambda: hop_grads(dq, dk_t, dv_t, kc, vc, src))

    dq0 = jnp.zeros((b, nh, s_pad, d), jnp.float32)
    zk = jnp.zeros((b, nkv, s_pad, d), jnp.float32)
    zv = jnp.zeros((b, nkv, s_pad, d), jnp.float32)

    quant = spec.wire != "fp32"
    if quant:
        from deepspeed_tpu.comm.quantized import (wire_decode_rows,
                                                  wire_encode_rows)

        kernel_dequant = spec.wire == "int8"

        def maybe_grads_q(dq, dk_t, dv_t, kp, ks, vp, vs, src):
            def live():
                if kernel_dequant:
                    return hop_grads(dq, dk_t, dv_t, kp, vp, src,
                                     ks=ks, vs=vs)
                kf = wire_decode_rows(kp, ks, spec.wire).astype(qk.dtype)
                vf = wire_decode_rows(vp, vs, spec.wire).astype(qk.dtype)
                return hop_grads(dq, dk_t, dv_t, kf, vf, src)

            if not masked:
                return live()
            return lax.cond(_hop_dead(idx, src, s_l, spec),
                            lambda: (dq, dk_t, dv_t), live)

        # own-block grads are exact (hop 0 never touches the wire and
        # the diagonal is never dead); encode once for the traveling copy
        dq, dk_t, dv_t = hop_grads(dq0, zk, zv, kk, vk, idx)
        kp, ks = wire_encode_rows(kk, spec.wire)
        vp, vs = wire_encode_rows(vk, spec.wire)
        # first rotation peeled out of the scan
        kp, vp, ks, vs, dk_t, dv_t = _rotate_kv_grads_quant(
            perm, spec.wire, kp, vp, ks, vs, dk_t, dv_t)

        def hop(carry, t):
            dq, dk_t, dv_t, kp, vp, ks, vs = carry
            src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
            if spec.interleave > 1:
                (nkp, nvp), (nks, nvs) = _rotate_quantized(
                    perm, (kp, vp), (ks, vs))
                dq, dk_t, dv_t = maybe_grads_q(dq, dk_t, dv_t, kp, ks,
                                               vp, vs, src)
                dk_t, dv_t = _rotate_grads_quant(perm, spec.wire,
                                                 dk_t, dv_t)
                return (dq, dk_t, dv_t, nkp, nvp, nks, nvs), None
            dq, dk_t, dv_t = maybe_grads_q(dq, dk_t, dv_t, kp, ks,
                                           vp, vs, src)
            kp, vp, ks, vs, dk_t, dv_t = _rotate_kv_grads_quant(
                perm, spec.wire, kp, vp, ks, vs, dk_t, dv_t)
            return (dq, dk_t, dv_t, kp, vp, ks, vs), None

        (dq, dk_t, dv_t, kp, vp, ks, vs), _ = lax.scan(
            hop, (dq, dk_t, dv_t, kp, vp, ks, vs),
            jnp.arange(spec.sp - 2))
        src_last = lax.rem(idx + 1, spec.sp)
        dq, dk_t, dv_t = maybe_grads_q(dq, dk_t, dv_t, kp, ks, vp, vs,
                                       src_last)
        # delivery hop: the traveling grads quantize one last time
        dk_t, dv_t = _rotate_grads_quant(perm, spec.wire, dk_t, dv_t)
        dq = dq[:, :, :s_l].swapaxes(1, 2).astype(ql.dtype)
        dk = dk_t[:, :, :s_l].swapaxes(1, 2).astype(kl.dtype)
        dv = dv_t[:, :, :s_l].swapaxes(1, 2).astype(vl.dtype)
        return dq, dk, dv

    # hop 0 = own block, first rotation peeled — same skeleton as the
    # quantized branch (census op-multiplicity symmetry)
    if spec.interleave > 1:
        # rotate-ahead: K/V depart before even the own-block grads
        kc, vc = _rotate_together(perm, kk, vk)
        dq, dk_t, dv_t = hop_grads(dq0, zk, zv, kk, vk, idx)
        dk_t, dv_t = _rotate_together(perm, dk_t, dv_t)
    else:
        dq, dk_t, dv_t = hop_grads(dq0, zk, zv, kk, vk, idx)
        # K/V and their accumulated grads rotate together, in one launch
        kc, vc, dk_t, dv_t = _rotate_together(perm, kk, vk, dk_t, dv_t)

    def hop(carry, t):
        dq, dk_t, dv_t, kc, vc = carry
        src = lax.rem(idx - t - 1 + spec.sp, spec.sp)
        if spec.interleave > 1:
            # rotate-ahead: same split as the XLA backward — K/V depart
            # under the fused grad kernels, traveling grads follow
            nkc, nvc = _rotate_together(perm, kc, vc)
            dq, dk_t, dv_t = maybe_grads(dq, dk_t, dv_t, kc, vc, src)
            dk_t, dv_t = _rotate_together(perm, dk_t, dv_t)
            return (dq, dk_t, dv_t, nkc, nvc), None
        dq, dk_t, dv_t = maybe_grads(dq, dk_t, dv_t, kc, vc, src)
        # K/V and their accumulated grads rotate together, in one launch
        kc, vc, dk_t, dv_t = _rotate_together(perm, kc, vc, dk_t, dv_t)
        return (dq, dk_t, dv_t, kc, vc), None

    (dq, dk_t, dv_t, kc, vc), _ = lax.scan(
        hop, (dq, dk_t, dv_t, kc, vc), jnp.arange(spec.sp - 2))
    src_last = lax.rem(idx + 1, spec.sp)
    dq, dk_t, dv_t = maybe_grads(dq, dk_t, dv_t, kc, vc, src_last)
    # the traveling grads sit one rank behind their owner — deliver home
    dk_t, dv_t = _rotate_together(perm, dk_t, dv_t)
    dq = dq[:, :, :s_l].swapaxes(1, 2).astype(ql.dtype)
    dk = dk_t[:, :, :s_l].swapaxes(1, 2).astype(kl.dtype)
    dv = dv_t[:, :, :s_l].swapaxes(1, 2).astype(vl.dtype)
    return dq, dk, dv


_ring_local.defvjp(_ring_fwd_rule, _ring_bwd_rule)


# ----------------------------------------------------------------------
# Public entry
# ----------------------------------------------------------------------
def ring_attention(q, k, v, topo=None, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   window: Optional[int] = None,
                   placement: str = "contiguous",
                   interleave: int = 1,
                   wire_dtype: str = "fp32"):
    """q/k/v: [B, S, H, D] GLOBAL arrays with S sharded over "seq".
    Returns [B, S, H, D].  GQA KV heads travel the ring unrepeated.  Must
    be called under jit (shard_map manual over the seq + batch axes; the
    head/tensor dims stay in GSPMD auto mode).

    ``placement``: how sequence blocks map to shards — "contiguous"
    (shard r owns rows [r·S_l, (r+1)·S_l)) or "striped" (shard r owns
    rows r, r+sp, …; the causal-load-balanced layout — see module
    docstring; the caller must feed striped data, cf.
    :func:`stripe_sequence`).

    ``wire_dtype`` (comm_quantization.ring_rotation): "fp32" = the raw
    word-packed rotation; "int8"/"fp8" = block-quantized payloads +
    per-row fp32 scales on the wire — K/V encoded once at ring entry,
    traveling dk/dv re-encoded per hop, dequant in the flash kernels'
    epilogues (int8 + the ``_kernel_enabled()`` gate) or via the shared
    XLA codec otherwise (docs/RING_ATTENTION.md, docs/QUANTIZED_COMM.md).
    Ignored at sp == 1 (no ring, nothing travels)."""
    topo = topo or get_topology()
    sp = topo.sp_size if topo is not None else 1
    nh, nkv = q.shape[2], k.shape[2]
    if nh % nkv:
        raise ValueError(
            f"ring_attention: num_heads={nh} not divisible by "
            f"kv_heads={nkv} — GQA requires an integer group size")
    if window is not None:
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        if not causal:
            raise ValueError(
                "window without causal would be a ONE-SIDED band "
                "(key ∈ (qpos-window, qpos+∞)), which is almost never "
                "intended; pass causal=True for Mistral-style sliding "
                "windows")
    if placement not in PLACEMENTS:
        raise ValueError(f"placement={placement!r}: expected one of "
                         f"{PLACEMENTS}")
    if interleave not in (1, 2):
        raise ValueError(f"interleave={interleave!r}: expected 1 (attend "
                         "then rotate) or 2 (rotate-ahead)")
    if wire_dtype != "fp32":
        from deepspeed_tpu.comm.quantized import validate_wire_dtype

        validate_wire_dtype(wire_dtype)
    rep = nh // nkv
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if sp == 1:
        if rep != 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return _block_attend_single(q, k, v, scale, causal, window)

    def body(ql, kl, vl):
        # the kernel gate reads the mesh context, so it is asked in here
        spec = _RingSpec(sp=sp, rep=rep, scale=float(scale), causal=causal,
                         window=window, placement=placement,
                         use_flash=_kernel_enabled(),
                         interleave=int(interleave),
                         wire=str(wire_dtype))
        return _ring_local(ql, kl, vl, spec)

    ctx = get_abstract_mesh()
    mesh = topo.mesh if ctx.empty else ctx
    # manual over seq + the batch axes (the ring only communicates over
    # "seq"; keeping batch sharded costs nothing) and over the size-1
    # axes (see with_unit_axes).  A >1 head/tensor axis stays in GSPMD
    # auto mode, so tensor-sharded heads are NOT gathered.
    pspec = P(BATCH_AXES, SEQ_AXIS, None, None)
    return shard_map(body, mesh=mesh, in_specs=(pspec, pspec, pspec),
                     out_specs=pspec,
                     axis_names=with_unit_axes(mesh, {SEQ_AXIS, *BATCH_AXES}),
                     check_vma=False)(q, k, v)


def _block_attend_single(q, k, v, scale, causal, window):
    """sp=1 degenerate form (same math, no ring)."""
    s_len = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = jnp.ones((s_len, s_len), bool)
    if causal:
        pos = jnp.arange(s_len)
        valid = pos[:, None] >= pos[None, :]
    if window is not None:
        pos = jnp.arange(s_len)
        valid &= (pos[:, None] - pos[None, :]) < window
    s = jnp.where(valid[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
