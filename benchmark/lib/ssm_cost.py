"""Operations and bytes the state-space scan of a ragged step needs, as
``lib.flops`` counts a kernel's: what the algorithm needs, not what a
kernel happens to do.

Per head, with state ``S`` in ``R^{P x N}``, a row costs ``S <- a S +
(dt x) B^T`` and ``y = S C``: ``4 P N`` FLOPs counting a multiply-add as
two and leaving the decay's multiply out (a chunked form regroups the
same products into matmuls; it needs no fewer).  A run of rows reads its
slot's float32 state once, unless it starts from zeros, and writes it
once, however many rows it has; every row reads ``x`` (``heads x P``),
``B`` and ``C`` (``groups x N`` each) and writes ``y`` at two bytes an
element, and reads its step ``dt`` (``heads``, float32).
"""

from __future__ import annotations


def ssd_cost(rows: int, state_bytes: float, layers: int, heads: int,
             head_dim: int, state: int, groups: int,
             bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the scan over ``rows`` rows in every one of
    ``layers`` layers, whose runs move ``state_bytes`` of recurrent state
    in all (every layer's, reads and writes: the ``state_bytes`` of the
    ``v2.schedule`` span)."""
    flops = 4.0 * head_dim * state * heads * rows * layers
    row_bytes = (bytes_per_el * (2 * heads * head_dim + 2 * groups * state)
                 + 4 * heads)
    return flops, float(state_bytes) + float(row_bytes) * rows * layers


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """``(seconds, "compute" | "memory")``: the larger of FLOPs over the
    peak FLOP/s and bytes over the peak bytes/s."""
    t_f = flops / peaks["flops_per_s_bf16"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), "compute" if t_f >= t_b else "memory"
