"""Block-sparse attention with DeepSpeed-compatible sparsity configs.

Analog of ``deepspeed/ops/sparse_attention/`` (``sparsity_config.py``
configs, ``sparse_self_attention.py``, Triton ``matmul.py``/``softmax.py``).
The reference builds a per-head block *layout* [H, nb, nb] and runs
Triton block-sparse kernels.  Here the same configs build the same
layouts; :func:`sparse_attention` dispatches to the Pallas block-sparse
kernel (ops/pallas/block_sparse_mha.py) on TPU — dead layout tiles are
skipped at the grid level, costing neither FLOPs nor K/V bandwidth, the
analog of the reference's Triton SDD/DSD block skipping — and falls back
to a dense attention masked at block granularity elsewhere.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


class SparsityConfig:
    """Base (ref sparsity_config.py SparsityConfig): block layout builder."""

    def __init__(self, num_heads: int, block: int = 16,
                 different_layout_per_head: bool = False):
        self.num_heads = num_heads
        self.block = block
        self.different_layout_per_head = different_layout_per_head

    def setup_layout(self, seq_len: int) -> np.ndarray:
        if seq_len % self.block != 0:
            raise ValueError(f"seq len {seq_len} not divisible by block "
                             f"{self.block}")
        nb = seq_len // self.block
        return np.zeros((self.num_heads, nb, nb), dtype=np.int64)

    def make_layout(self, seq_len: int) -> np.ndarray:
        raise NotImplementedError


class DenseSparsityConfig(SparsityConfig):
    """All blocks attend (ref DenseSparsityConfig) — for testing parity."""

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        layout[:] = 1
        return layout


class FixedSparsityConfig(SparsityConfig):
    """Local windows + periodic global blocks (ref FixedSparsityConfig).

    Each query block attends its own ``num_local_blocks`` window plus the
    last ``num_global_blocks`` of every window (the "summary" blocks).
    """

    def __init__(self, num_heads: int, block: int = 16,
                 num_local_blocks: int = 4, num_global_blocks: int = 1,
                 attention: str = "bidirectional",
                 horizontal_global_attention: bool = False, **kw):
        super().__init__(num_heads, block, kw.get("different_layout_per_head", False))
        self.num_local_blocks = num_local_blocks
        self.num_global_blocks = num_global_blocks
        self.attention = attention
        self.horizontal_global_attention = horizontal_global_attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        nb = layout.shape[1]
        for q in range(nb):
            w0 = (q // self.num_local_blocks) * self.num_local_blocks
            # local window
            for k in range(w0, min(w0 + self.num_local_blocks, nb)):
                layout[:, q, k] = 1
            # global (summary) blocks: last num_global_blocks of each
            # preceding window
            for wstart in range(0, nb, self.num_local_blocks):
                gstart = wstart + self.num_local_blocks - self.num_global_blocks
                for k in range(max(wstart, gstart), min(wstart + self.num_local_blocks, nb)):
                    if k <= q or self.attention == "bidirectional":
                        layout[:, q, k] = 1
                    if self.horizontal_global_attention:
                        layout[:, k, q] = 1
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


class BSLongformerSparsityConfig(SparsityConfig):
    """Sliding window + explicit global blocks (ref
    BSLongformerSparsityConfig)."""

    def __init__(self, num_heads: int, block: int = 16,
                 num_sliding_window_blocks: int = 3,
                 global_block_indices=(0,), attention: str = "bidirectional",
                 **kw):
        super().__init__(num_heads, block, kw.get("different_layout_per_head", False))
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.global_block_indices = list(global_block_indices)
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        nb = layout.shape[1]
        half = self.num_sliding_window_blocks // 2
        for q in range(nb):
            for k in range(max(0, q - half), min(nb, q + half + 1)):
                layout[:, q, k] = 1
        for g in self.global_block_indices:
            if g < nb:
                layout[:, g, :] = 1  # global row
                layout[:, :, g] = 1  # global column
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


class BigBirdSparsityConfig(SparsityConfig):
    """Random + sliding + global blocks (ref BigBirdSparsityConfig)."""

    def __init__(self, num_heads: int, block: int = 16,
                 num_random_blocks: int = 1, num_sliding_window_blocks: int = 3,
                 num_global_blocks: int = 1, attention: str = "bidirectional",
                 seed: int = 0, **kw):
        super().__init__(num_heads, block,
                         kw.get("different_layout_per_head", False))
        self.num_random_blocks = num_random_blocks
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.num_global_blocks = num_global_blocks
        self.attention = attention
        self.seed = seed

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        nb = layout.shape[1]
        half = self.num_sliding_window_blocks // 2
        rng = np.random.default_rng(self.seed)
        for h in range(self.num_heads if self.different_layout_per_head else 1):
            for q in range(nb):
                for k in range(max(0, q - half), min(nb, q + half + 1)):
                    layout[h, q, k] = 1
                ks = rng.choice(nb, size=min(self.num_random_blocks, nb),
                                replace=False)
                layout[h, q, ks] = 1
            g = self.num_global_blocks
            layout[h, :g, :] = 1
            layout[h, :, :g] = 1
        if not self.different_layout_per_head:
            layout[:] = layout[0]
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


class VariableSparsityConfig(SparsityConfig):
    """Per-head variable local windows + globals (ref
    VariableSparsityConfig, simplified: explicit window list)."""

    def __init__(self, num_heads: int, block: int = 16,
                 local_window_blocks=(4,), global_block_indices=(0,),
                 attention: str = "bidirectional", **kw):
        super().__init__(num_heads, block, True)
        self.local_window_blocks = list(local_window_blocks)
        self.global_block_indices = list(global_block_indices)
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        layout = self.setup_layout(seq_len)
        nb = layout.shape[1]
        for h in range(self.num_heads):
            w = self.local_window_blocks[min(h, len(self.local_window_blocks) - 1)]
            for q in range(nb):
                w0 = (q // w) * w
                layout[h, q, w0:min(w0 + w, nb)] = 1
        for g in self.global_block_indices:
            if g < nb:
                layout[:, g, :] = 1
                layout[:, :, g] = 1
        if self.attention == "unidirectional":
            layout = np.tril(layout)
        return layout


# ----------------------------------------------------------------------
def layout_to_token_mask(layout: np.ndarray, block: int) -> jnp.ndarray:
    """[H, nb, nb] block layout → [H, S, S] boolean token mask."""
    m = jnp.asarray(layout, jnp.bool_)
    return jnp.repeat(jnp.repeat(m, block, axis=1), block, axis=2)


def sparse_attention(q, k, v, sparsity_config: SparsityConfig,
                     causal: bool = False,
                     sm_scale: Optional[float] = None,
                     impl: str = "auto") -> jnp.ndarray:
    """Block-sparse attention (ref SparseSelfAttention forward).

    q/k/v: [B, S, H, D] → [B, S, H, D] (GQA: k/v may carry fewer heads).
    The block layout masks the score matrix; causal composes a
    lower-triangular mask on top.  ``impl='auto'`` takes the Pallas
    block-skipping kernel on TPU (ops/pallas/block_sparse_mha.py — dead
    layout tiles cost neither FLOPs nor K/V DMA, the reference's Triton
    matmul.py behavior); ``'xla'`` forces the dense-masked lowering.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = q.shape[1]
    layout = sparsity_config.make_layout(s)

    if impl in ("auto", "pallas"):
        import importlib

        bsm = importlib.import_module(
            "deepspeed_tpu.ops.pallas.block_sparse_mha")
        fm = importlib.import_module("deepspeed_tpu.ops.pallas.flash_mha")
        lb = sparsity_config.block
        ok = (s % lb == 0 and bsm.supports(s, q.shape[-1], lb, q.shape[2],
                                           layout_heads=layout.shape[0]))
        if impl == "pallas" and not ok:
            raise ValueError(
                f"impl='pallas' but the block-sparse kernel does not apply "
                f"(seq {s}, block {lb}, heads {q.shape[2]} vs layout "
                f"{layout.shape[0]}) — fix the config or use impl='auto'")
        # "auto" takes the kernel only under the interpreter: the chip's
        # compiler refuses its layout-mask BlockSpec at every shape (PR 23
        # compile rehearsal; ROADMAP D3), so on TPU the dense path below
        # is the one that runs.  impl="pallas" still asks for the kernel
        # and gets the compiler's own error.
        if (fm.INTERPRET or impl == "pallas") and ok:
            out = bsm.block_sparse_mha(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), layout, lb, causal=causal,
                sm_scale=sm_scale)
            return out.transpose(0, 2, 1, 3)

    if k.shape[2] != q.shape[2]:  # GQA: expand kv heads for the dense path
        g = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    mask = layout_to_token_mask(layout, sparsity_config.block)  # [H, S, S]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * sm_scale,
                        k.astype(jnp.float32))
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask[None], scores, neg)
    if causal:
        cm = jnp.tril(jnp.ones((s, s), jnp.bool_))
        scores = jnp.where(cm[None, None], scores, neg)
    # rows with no visible keys (can happen off-layout) → uniform zeros
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isfinite(scores).any(-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
