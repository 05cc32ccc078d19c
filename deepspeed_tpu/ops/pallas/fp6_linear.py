"""FP6 (e3m2) packed-weight linear: real 6-bit storage + a Pallas GEMM
that unpacks in VMEM.

TPU-native analog of the reference's FP6-LLM weight-only path
(``inference/v2/kernels/core_ops/cuda_linear/cuda_linear.py:167`` — packed
6-bit storage + split-K GEMM): weights live in HBM as 0.75 bytes/value
(plus one fp32 scale per output column), and the matmul kernel reads ONLY
the packed bytes, decoding e3m2 → bf16 inside VMEM right before the MXU
dot.  Serving is weight-bandwidth-bound, so reading 6 bits instead of 16
is both the memory saving at rest AND the bandwidth win per step — the
property the quant-dequant emulation in ``ops/fp_quantizer.py`` cannot
provide.

Layout: the [K, N] weight's K dim is viewed in groups of 4 values
v0..v3 (6 bits each = 3 bytes), stored as three byte PLANES
``packed[3, K/4, N]``:

    B0 = v0<<2 | v1>>4;  B1 = (v1&15)<<4 | v2>>2;  B2 = (v2&3)<<6 | v3

Plane-major packing means the kernel never interleaves along sublanes:
the activation is pre-split into 4 K-strided planes ``x4[4, M, K/4]``
(``x[:, p::4]``), and the tile dot is the sum of 4 plane dots — the
split-K structure of the reference GEMM, with K-grid accumulation in an
f32 VMEM scratch.

e3m2: 1 sign, 3 exponent (bias 3, full range — no inf/nan codes),
2 mantissa; max normal 28.0, subnormal step 2^-4.  Encoding snaps to the
nearest representable value (host-side, at weight-load time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.jax_compat import tpu_compiler_params
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.platform import on_tpu

INTERPRET = False

# One-time flag: the dequantize-then-dot fallback silently reads 16-bit
# weights (the whole point of fp6 is the 6-bit wire/HBM read), so losing
# the bandwidth win must be visible in logs exactly once per process.
_warned_fallback = False

_BIAS = 3
_MAX_VAL = 28.0  # (2 - 2^-2) * 2^(7-3): full exponent range, no inf/nan


def _decode_table() -> np.ndarray:
    """All 64 e3m2 code values (index = 6-bit code)."""
    codes = np.arange(64)
    s = codes >> 5
    e = (codes >> 2) & 7
    m = (codes & 3).astype(np.float64)
    mag = np.where(e == 0, m * 2.0 ** (1 - _BIAS - 2),
                   (1.0 + m * 0.25) * 2.0 ** (e - _BIAS))
    return np.where(s == 1, -mag, mag).astype(np.float32)


DECODE_TABLE = _decode_table()


def fp6_quantize(w) -> tuple:
    """[K, N] weight → (packed uint8 [3, K/4, N], scale fp32 [N]).

    Per-output-column absmax scaling (the reference's per-channel
    quantization), nearest-representable e3m2 encoding, plane packing.
    Host-side numpy — runs once at weight-load time."""
    w = np.asarray(w, np.float32)
    k, n = w.shape
    if k % 4:
        raise ValueError(f"K={k} must be divisible by 4 for fp6 packing")
    scale = np.maximum(np.abs(w).max(axis=0), 1e-12) / _MAX_VAL   # [N]
    ws = w / scale[None, :]
    # nearest representable value via searchsorted on the sorted table
    order = np.argsort(DECODE_TABLE, kind="stable")
    tbl = DECODE_TABLE[order]
    pos = np.searchsorted(tbl, ws).clip(1, 63)
    lo, hi = tbl[pos - 1], tbl[np.minimum(pos, 63)]
    pick_hi = (ws - lo) > (hi - ws)
    codes = order[np.where(pick_hi, np.minimum(pos, 63), pos - 1)]
    codes = codes.astype(np.uint8)                                # [K, N]
    v = codes.reshape(k // 4, 4, n)
    v0, v1, v2, v3 = v[:, 0], v[:, 1], v[:, 2], v[:, 3]
    packed = np.stack([
        (v0 << 2) | (v1 >> 4),
        ((v1 & 15) << 4) | (v2 >> 2),
        ((v2 & 3) << 6) | v3,
    ]).astype(np.uint8)                                           # [3,K/4,N]
    return jnp.asarray(packed), jnp.asarray(scale, jnp.float32)


def _unpack_codes(packed):
    """[3, K/4, N] planes → 4 code planes v0..v3 (int32 [K/4, N])."""
    b0 = packed[0].astype(jnp.int32)
    b1 = packed[1].astype(jnp.int32)
    b2 = packed[2].astype(jnp.int32)
    v0 = b0 >> 2
    v1 = ((b0 & 3) << 4) | (b1 >> 4)
    v2 = ((b1 & 15) << 2) | (b2 >> 6)
    v3 = b2 & 63
    return v0, v1, v2, v3


def _decode(v):
    """e3m2 code plane (int32) → f32 values, arithmetically (no table
    gather — VPU-friendly)."""
    s = v >> 5
    e = (v >> 2) & 7
    m = (v & 3).astype(jnp.float32)
    mag = jnp.where(e == 0, m * 2.0 ** (1 - _BIAS - 2),
                    (1.0 + m * 0.25) * jnp.exp2((e - _BIAS)
                                                .astype(jnp.float32)))
    return jnp.where(s == 1, -mag, mag)


def fp6_dequantize(packed, scale, dtype=jnp.bfloat16):
    """Full dequantized [K, N] weight (XLA fallback / tests)."""
    k4 = packed.shape[1]
    n = packed.shape[2]
    planes = [_decode(v) for v in _unpack_codes(packed)]
    w = jnp.stack(planes, axis=1).reshape(k4 * 4, n)
    return (w * scale[None, :]).astype(dtype)


def _mm_kernel(x_ref, p_ref, sc_ref, o_ref, acc, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    v0, v1, v2, v3 = _unpack_codes(p_ref[...])
    part = jnp.zeros_like(acc)
    for p, v in enumerate((v0, v1, v2, v3)):
        part += jax.lax.dot_general(
            x_ref[p], _decode(v).astype(x_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc[...] += part

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc[...] * sc_ref[0][None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k4"))
def fp6_matmul(x, packed, scale, block_m: int = 256, block_n: int = 256,
               block_k4: int = 128):
    """``x [M, K] @ fp6_weight [K, N]`` reading only packed bytes.

    The kernel consumes the activation as 4 K-strided planes and sums 4
    plane dots per tile (split-K over the plane structure), accumulating
    across the K grid in f32 scratch.  Falls back to the XLA
    dequantize-then-dot form off-TPU unless INTERPRET."""
    lead = x.shape[:-1]
    if x.ndim != 2:
        # [..., K] activations (e.g. [B, S, H]) flatten to rows
        x = x.reshape(-1, x.shape[-1])
    m, k = x.shape
    _, k4, n = packed.shape
    if k4 * 4 != k:
        raise ValueError(f"packed K {k4 * 4} != x K {k}")
    run_kernel = INTERPRET or on_tpu()
    # Awkward M (prime, 2·prime, …) would degenerate the largest-divisor
    # tile into 1-2 rows; pad M up to a multiple of 8 (sublane) instead —
    # a few zero rows beat either tiny tiles or falling back to reading
    # the full dequantized weight on this weight-bandwidth-bound path.
    m_pad = -(-m // 8) * 8
    if m_pad != m:
        x = jnp.concatenate(
            [x, jnp.zeros((m_pad - m, k), x.dtype)], axis=0)
    bm = next((c for c in range(min(block_m, m_pad), 7, -1)
               if m_pad % c == 0), 8)
    bn = min(block_n, n)
    bk4 = min(block_k4, k4)
    servable = (n % bn == 0 and k4 % bk4 == 0
                and bn % 128 == 0 and bk4 % 8 == 0)
    if not servable or not run_kernel:
        global _warned_fallback
        if not _warned_fallback:
            reason = (f"unservable tile shape (K={k}, N={n} vs blocks "
                      f"bn={bn}, bk4={bk4})" if run_kernel
                      else "not running on TPU")
            logger.warning(
                "fp6_matmul: %s — falling back to dequantize-then-dot; the "
                "packed 6-bit HBM/bandwidth win is lost for these calls "
                "(weights are expanded to %s before the MXU dot)",
                reason, jnp.dtype(x.dtype).name)
            _warned_fallback = True
        out = x[:m] @ fp6_dequantize(packed, scale, x.dtype)
        return out.reshape(lead + (n,))
    m = m_pad

    x4 = x.reshape(m, k4, 4).swapaxes(0, 2).swapaxes(1, 2)  # [4, M, K/4]
    nk = k4 // bk4
    out = pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((4, bm, bk4), lambda i, j, k_: (0, i, k_)),
            pl.BlockSpec((3, bk4, bn), lambda i, j, k_: (0, k_, j)),
            pl.BlockSpec((1, bn), lambda i, j, k_: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=INTERPRET,
    )(x4, packed, scale.reshape(1, n))
    rows = int(np.prod(lead, dtype=np.int64)) if lead else 1
    return out[:rows].reshape(lead + (n,))
