"""Blockwise integer quantization kernels.

TPU-native analog of the reference's quantization kernel set
(``csrc/quantization/``: quantize.cu, dequantize.cu, fake_quantizer.cu,
swizzled_quantize.cu, quant_reduce.cu — SURVEY §2.6).  On CUDA these are
hand-written warp kernels; on TPU the same math is plain jittable jnp that
XLA fuses into neighbouring ops (gather/scatter/reduce), so there is no
separate "kernel launch" — the quantize fuses into the collective's
producer and the dequantize into its consumer.

Swizzled layouts (swizzled_quantize.cu) exist on CUDA to coalesce the
subsequent NCCL transfer; XLA's layout assignment owns tiling on TPU, so no
swizzle variant is needed — noted here for parity auditing.  The claim
that the TRANSPORT really moves int8 (the whole point of qwZ/qgZ) is
pinned at the compiled-HLO level by tests/test_quant_transport.py: the
ZeRO++ all-gather and both qgZ all-to-all hops carry s8 payloads with no
full-size float collective remaining.

All functions are symmetric-by-default blockwise: the last axis is grouped
into ``group_size`` blocks, each with its own scale (and zero-point when
asymmetric).  int4 packs two nibbles per int8 byte for wire/memory savings.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.platform import on_tpu


def _pallas_ok(x: jnp.ndarray, num_bits: int, group_size: int,
               symmetric: bool, backend: str) -> bool:
    """Route to the Pallas kernels (ops/pallas/quantize.py) when requested
    and servable: 'pallas' forces them, 'auto' uses them on TPU only (the
    CPU interpreter is test-grade), 'jnp' never."""
    if backend == "jnp":
        return False
    from deepspeed_tpu.ops.pallas import quantize as pq

    if not pq.supports(x.shape, group_size, symmetric, num_bits):
        return False
    return backend == "pallas" or pq.INTERPRET or on_tpu()


def _group(x: jnp.ndarray, group_size: int) -> Tuple[jnp.ndarray, int]:
    n = x.shape[-1]
    if group_size <= 0 or group_size > n:
        group_size = n
    if n % group_size != 0:
        raise ValueError(f"last dim {n} not divisible by group_size {group_size}")
    return x.reshape(x.shape[:-1] + (n // group_size, group_size)), group_size


def quantize_blockwise(x: jnp.ndarray, num_bits: int = 8, group_size: int = 256,
                       symmetric: bool = True,
                       backend: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray,
                                                       Optional[jnp.ndarray]]:
    """Quantize to ``num_bits`` integers with per-group scales.

    Returns ``(q, scale, zero_point)``; ``zero_point`` is None when
    symmetric.  q is int8 (int4 values occupy the low nibble range).
    ``backend``: 'auto' (Pallas on TPU when servable, else jnp),
    'pallas', or 'jnp'.
    Ref: csrc/quantization/quantize.cu / pt_binding quantize.
    """
    if _pallas_ok(x, num_bits, group_size, symmetric, backend):
        from deepspeed_tpu.ops.pallas import quantize as pq

        q, s = pq.quantize(x, num_bits, group_size)
        return q, s, None
    g, group_size = _group(x.astype(jnp.float32), group_size)
    qmax = float(2 ** (num_bits - 1) - 1)
    if symmetric:
        absmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
        scale = absmax / qmax
        scale = jnp.where(scale == 0, 1.0, scale)
        q = jnp.clip(jnp.round(g / scale), -qmax - 1, qmax).astype(jnp.int8)
        return q.reshape(x.shape), scale.squeeze(-1), None
    lo = jnp.min(g, axis=-1, keepdims=True)
    hi = jnp.max(g, axis=-1, keepdims=True)
    scale = (hi - lo) / (2 ** num_bits - 1)
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round((g - lo) / scale), 0, 2 ** num_bits - 1)
    # store centred so int8 holds uint range for 8-bit too
    q = (q - 2 ** (num_bits - 1)).astype(jnp.int8)
    return q.reshape(x.shape), scale.squeeze(-1), lo.squeeze(-1)


def dequantize_blockwise(q: jnp.ndarray, scale: jnp.ndarray,
                         zero_point: Optional[jnp.ndarray] = None,
                         num_bits: int = 8,
                         dtype=jnp.float32,
                         backend: str = "auto") -> jnp.ndarray:
    """Inverse of :func:`quantize_blockwise` (ref dequantize.cu)."""
    if zero_point is None and _pallas_ok(
            q, num_bits, q.shape[-1] // scale.shape[-1], True, backend):
        from deepspeed_tpu.ops.pallas import quantize as pq

        return pq.dequantize(q, scale, dtype=dtype)
    shape = q.shape
    group_size = shape[-1] // scale.shape[-1]
    g = q.astype(jnp.float32).reshape(shape[:-1] + (scale.shape[-1], group_size))
    if zero_point is None:
        out = g * scale[..., None]
    else:
        out = (g + 2 ** (num_bits - 1)) * scale[..., None] + zero_point[..., None]
    return out.reshape(shape).astype(dtype)


def fake_quantize(x: jnp.ndarray, num_bits: int = 8, group_size: int = 256,
                  symmetric: bool = True, backend: str = "auto") -> jnp.ndarray:
    """Quantize-dequantize roundtrip for QAT (ref fake_quantizer.cu).  The
    Pallas route does it in one HBM pass (payload stays in VMEM)."""
    if _pallas_ok(x, num_bits, group_size, symmetric, backend):
        from deepspeed_tpu.ops.pallas import quantize as pq

        return pq.fake_quantize(x, num_bits, group_size)
    q, s, z = quantize_blockwise(x, num_bits, group_size, symmetric,
                                 backend="jnp")
    return dequantize_blockwise(q, s, z, num_bits, dtype=x.dtype,
                                backend="jnp")


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 values (stored in int8) into one byte per pair — halves
    wire/HBM footprint for quantized collectives (ref quant_reduce.cu uses
    4-bit lanes)."""
    if q.shape[-1] % 2 != 0:
        raise ValueError("last dim must be even to pack int4")
    lo = q[..., 0::2].astype(jnp.uint8) & 0xF
    hi = (q[..., 1::2].astype(jnp.uint8) & 0xF) << 4
    return (lo | hi).astype(jnp.uint8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    # sign-extend nibbles
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(packed.shape[:-1] + (packed.shape[-1] * 2,))


def stochastic_round(x: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
    """Stochastic rounding helper (ref sr_fused kernels): round up with
    probability equal to the fractional part."""
    floor = jnp.floor(x)
    frac = x - floor
    return floor + (jax.random.uniform(key, x.shape) < frac).astype(x.dtype)
