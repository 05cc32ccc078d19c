"""Operations and bytes the learned indexer of a latent-attention model
needs for a ragged step, as ``lib.flops`` and ``lib.ssm_cost`` count a
kernel's: what the algorithm needs, not what a kernel happens to do.

For a row ``t`` and a key ``s`` it may see, ``I[t, s] = sum_j w[t, j] *
relu(q[t, j] . k[s])`` over ``heads`` index heads of ``dim``: ``2 * dim``
FLOPs a head and pair for the dot product, counting a multiply-add as two
and leaving out the ReLU, the weight's multiply and the sum over heads
(three operations beside 2 x 128).  Every (row, key) pair is one float32
score written; a sequence's keys are read once a step however many of its
rows score them (a chunk's rows share them), two bytes an element; a row
reads its ``heads`` queries and float32 weights.
"""

from __future__ import annotations

from benchmark.lib.ssm_cost import least_time  # noqa: F401  (the same rule)


def index_scores_cost(pairs: int, rows: int, ctx_keys: int, layers: int,
                      heads: int, dim: int,
                      bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the indexer's scores in every one of ``layers``
    indexed layers: ``pairs`` (row, visible key) pairs (``index_pairs`` of
    the ``v2.schedule`` span), ``rows`` new rows (``latent_rows``),
    ``ctx_keys`` keys of the step's sequences, each counted once
    (``kv_rows``)."""
    flops = 2.0 * dim * heads * pairs * layers
    nbytes = layers * (4.0 * pairs + float(bytes_per_el) * dim * ctx_keys
                       + float(rows) * heads * (bytes_per_el * dim + 4))
    return flops, nbytes
