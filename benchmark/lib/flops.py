"""Operations and bytes from shapes.

The model arithmetic is a copy of ``deepspeed_tpu/telemetry/derive.py``
(``fwd_flops_per_tok``, ``mfu``); the kernel counts are the benchmark's own.
All of it counts what the algorithm needs, not what a kernel happens to
do: recomputed operations do not count, masked-out score tiles do not
count, and every tensor is counted as read or written once.
"""

from __future__ import annotations

FWD_BWD_FACTOR = 3.0        # backward = 2 x forward


def fwd_flops_per_tok(hidden: int, layers: int, vocab: int, ffn: int,
                      heads: int, kv_heads: int, gated: bool,
                      seq: int) -> float:
    """Forward FLOPs per token of a dense decoder: q, k, v, o projections
    (k and v at the grouped width), the feed-forward matmuls (three when
    gated), the output head, and causal attention at context ``seq``
    (QK^T and PV, each 2*seq*hidden per token, halved by the mask)."""
    qkvo = 2 * hidden * hidden + 2 * hidden * (hidden * kv_heads // heads)
    matmul = layers * (qkvo + (3 if gated else 2) * hidden * ffn)
    return 2.0 * matmul + 2.0 * hidden * vocab + 2.0 * seq * hidden * layers


def mfu(tokens_per_s: float, fwd_flops_tok: float, chips: int,
        peak_flops_per_s: float) -> float:
    """Model FLOP/s utilisation of a training run: forward and backward
    operations the model requires, over the peak of all chips used."""
    return (tokens_per_s * FWD_BWD_FACTOR * fwd_flops_tok
            / (chips * peak_flops_per_s))


def flash_attention_cost(batch: int, q_heads: int, kv_heads: int,
                         seq_q: int, seq_kv: int, head_dim: int,
                         causal: bool = True, window: int | None = None,
                         backward: bool = False,
                         bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one flash-attention call needs.

    Forward: QK^T and PV are 2*head_dim FLOPs each per live (query, key)
    pair.  Live pairs: all ``seq_q * seq_kv`` without a mask; under a
    causal mask with the queries at the end of the keys, query i (of
    seq_q, 0-based) sees ``seq_kv - seq_q + i + 1`` keys, cut to
    ``window`` where one is set.  Backward needs 2.5 x the forward's
    matmuls (dQ, dK, dV, and the recomputed scores count once as the
    algorithm's own).  Bytes forward: Q and O once, K and V once; backward
    reads Q, K, V, O, dO and writes dQ, dK, dV.
    """
    if causal:
        base = seq_kv - seq_q
        pairs = 0
        for i in range(seq_q):
            seen = base + i + 1
            pairs += min(seen, window) if window else seen
    else:
        pairs = seq_q * seq_kv
    fwd = 4.0 * head_dim * pairs * batch * q_heads
    q_el = batch * q_heads * seq_q * head_dim
    kv_el = batch * kv_heads * seq_kv * head_dim
    if not backward:
        return fwd, float(bytes_per_el * (2 * q_el + 2 * kv_el))
    return 2.5 * fwd, float(bytes_per_el * (5 * q_el + 4 * kv_el))


def paged_decode_cost(ctx_lens, q_heads: int, kv_heads: int, head_dim: int,
                      window: int | None = None,
                      bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one paged-attention decode call needs for one new
    token per sequence at the given context lengths: each sequence reads
    its keys and values once (``kv_heads`` wide) and does QK^T and PV over
    them for every query head."""
    flops = 0.0
    nbytes = 0.0
    for c in ctx_lens:
        live = min(c, window) if window else c
        flops += 4.0 * head_dim * live * q_heads
        nbytes += bytes_per_el * (2 * live * kv_heads * head_dim
                                  + 2 * q_heads * head_dim)
    return flops, nbytes
