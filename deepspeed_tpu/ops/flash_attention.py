"""Flash attention for TPU.

Replaces the reference's fused attention CUDA kernels
(``csrc/transformer``/FlashAttention paths). The default TPU path is the
**repo-owned** Pallas kernel (`deepspeed_tpu.ops.pallas.flash_mha`):
GQA-native (KV never repeated), any sequence length (tail-pad + in-kernel
mask — no silent O(S²) fallback), saved-residual backward. The upstream
jax library kernel remains available as ``impl="pallas_lib"``; non-TPU
backends (the 8-device CPU test mesh) use a numerically equivalent XLA
implementation so the same model code runs everywhere.

Layout contract: q, k, v are ``[batch, seq, heads, head_dim]`` (the model's
natural layout); the kernels operate in ``[batch, heads, seq, head_dim]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import (BATCH_AXES, SEQ_AXIS,
                                             TENSOR_AXIS, get_topology)
from deepspeed_tpu.utils.jax_compat import (get_abstract_mesh,
                                            manual_axis_names, shard_map)
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.platform import on_tpu

_warned_fallback = False


def _under_mesh(kernel, q, k, v):
    """Run ``kernel(q, k, v)`` ([B, S, H, D] in and out) where the traced
    program spans several devices.  Mosaic kernels cannot be partitioned
    automatically: the compiler takes one only inside a ``shard_map`` that
    is manual over every mesh axis.  Batch rides the batch axes and heads
    the tensor/seq axes — the layout tensor parallelism and Ulysses give
    q/k/v at this point — wherever they divide; over any other axis the
    kernel runs replicated."""
    topo = get_topology()
    if topo is None or topo.world_size == 1:
        return kernel(q, k, v)
    ctx = get_abstract_mesh()
    mesh = topo.mesh if ctx.empty else ctx
    auto = set(mesh.axis_names) - manual_axis_names()
    if not auto:
        return kernel(q, k, v)

    def dividing(axes, n):
        axes = tuple(a for a in axes if a in auto and topo.axis_size(a) > 1)
        size = math.prod(topo.axis_size(a) for a in axes)
        return axes if axes and n % size == 0 else None

    spec = P(dividing(BATCH_AXES, q.shape[0]), None,
             dividing((TENSOR_AXIS, SEQ_AXIS),
                      math.gcd(q.shape[2], k.shape[2])), None)
    return shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, axis_names=auto)(q, k, v)


def _repeat_kv(q, k, v):
    """Repeat KV heads up to the query head count (GQA -> MHA) for the
    paths whose kernels are not GQA-native."""
    nh, nkv = q.shape[2], k.shape[2]
    if nkv != nh:
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
    return k, v


def _xla_attention(q, k, v, causal: bool, sm_scale: float,
                   window: int | None = None):
    b, s_q, h, d = q.shape
    k, v = _repeat_kv(q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
    s_k = k.shape[1]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
    if window is not None:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0) + (s_k - s_q)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        wm = qpos - kpos < window
        mask = wm if mask is None else mask & wm
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_for(s: int, max_block: int = 512) -> int | None:
    """Largest block ≤ max_block that divides ``s`` and is a multiple of
    the 128-lane register width; None if the library kernel can't tile
    ``s``."""
    for blk in range(min(max_block, s), 127, -128):
        if blk % 128 == 0 and s % blk == 0:
            return blk
    return None


def _lib_flash(q, k, v, causal, sm_scale, blk):
    """Upstream jax.experimental Pallas kernel (KV repeated to MHA)."""
    k, v = _repeat_kv(q, k, v)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention as pallas_flash)

    qt = q.swapaxes(1, 2)  # [B, H, S, D]
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    sizes = BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
        block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
    out = pallas_flash(qt, kt, vt, causal=causal, sm_scale=sm_scale,
                       block_sizes=sizes)
    return out.swapaxes(1, 2)


def flash_executed_shares(seq: int, head_dim: int, group: int, causal: bool,
                          window: int | None = None):
    """``(forward, backward)``: the (query, key) pairs the repo kernels
    execute for one head of such a call over ``seq²`` (0.5 is all a
    causal mask keeps; ``flash_mha.plan`` has the decision), or None
    where ``flash_attention(impl="auto")`` does not run them: off the
    TPU, or past the kernels' budget."""
    from deepspeed_tpu.ops.pallas.flash_mha import plan, supports

    if not on_tpu() or not supports(seq, head_dim):
        return None
    p = plan(seq, head_dim, group, causal, window or None)
    return p.executed_share_fwd, p.executed_share_bwd


def flash_attention(q, k, v, causal: bool = True, sm_scale: float | None = None,
                    impl: str = "auto", window: int | None = None):
    """Multi-head attention over [B, S, H, D] tensors.

    ``impl``: "auto" (repo Pallas kernel on TPU, XLA elsewhere) | "pallas"
    (repo kernel) | "pallas_lib" (upstream library kernel) | "xla".

    Not jitted itself: the dispatch reads the platform and the process's
    mesh topology, neither of which a jit cache would key on.
    """
    global _warned_fallback
    if window is not None and window <= 0:
        raise ValueError(f"window must be positive (got {window}); pass "
                         "None to disable sliding-window masking")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])

    if impl == "xla" or not (impl in ("auto", "pallas", "pallas_lib")
                             and on_tpu()):
        return _xla_attention(q, k, v, causal, sm_scale, window=window)

    def lib_flash(blk):
        return _under_mesh(functools.partial(
            _lib_flash, causal=causal, sm_scale=sm_scale, blk=blk), q, k, v)

    if impl == "pallas_lib":
        if window is not None:  # library kernel has no window support
            impl = "pallas"
        else:
            blk = _block_for(q.shape[1])
            if blk is None:
                if not _warned_fallback:
                    logger.warning(
                        "flash_attention: seq %d has no 128-aligned divisor; "
                        "library kernel unavailable, using XLA attention",
                        q.shape[1])
                    _warned_fallback = True
                return _xla_attention(q, k, v, causal, sm_scale,
                                      window=window)
            return lib_flash(blk)

    from deepspeed_tpu.ops.pallas import flash_mha
    from deepspeed_tpu.ops.pallas.flash_mha import supports

    if not supports(q.shape[1], q.shape[-1]):
        # beyond even the KV-blocked path's ceiling (S·D > 2^25) — shard
        # the sequence (Ulysses/FPDT) at such lengths. Last resorts: the
        # library kernel (repeats KV, no window), then XLA.
        blk = _block_for(q.shape[1]) if window is None else None
        if blk is not None:
            return lib_flash(blk)
        if not _warned_fallback:
            logger.warning(
                "flash_attention: seq %d (head_dim %d) exceeds kernel "
                "budgets; using XLA attention", q.shape[1], q.shape[-1])
            _warned_fallback = True
        return _xla_attention(q, k, v, causal, sm_scale, window=window)

    def kernel(q, k, v):
        out = flash_mha(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                        causal, sm_scale, window)
        return out.swapaxes(1, 2)

    return _under_mesh(kernel, q, k, v)
