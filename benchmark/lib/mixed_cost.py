"""Operations and bytes the paged attention reads of a ragged step need
in a model whose KINDS of layer differ in shape (window and full layers
with KV heads of their own, keys wider than values), as ``lib.flops``
counts a kernel's: what the algorithm needs at the PUBLISHED widths, not
what a kernel or a pool's layout happens to move.

A live (query row, key) pair of one query head costs the score's ``2 x
key width`` and the value product's ``2 x value width`` FLOPs (a
multiply-add as two); the softmax and a sink's one exponential are left
out.  A layer of a kind reads each key row and value row of its
sequences' live contexts ONCE a step (``kv heads x (key width + value
width)`` elements a row: the published 192 + 128, never the 256 lanes a
pool keeps a key row in, so padding shows as a lower share of the
roofline and not as more work), reads the step's query rows once and
writes as many output rows (``heads x key width`` and ``heads x value
width`` elements a row).
"""

from __future__ import annotations


def read_cost(pairs: float, kv_rows: float, rows: float, layers: int,
              heads: int, kv_heads: int, key_width: int, value_width: int,
              bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the attention reads of ``layers`` layers of ONE
    kind over a step (or several: the counts add): ``pairs`` live (query
    row, key) pairs of one layer of the kind, ``kv_rows`` key rows its
    rows read (each sequence's context once, cut to the window), ``rows``
    query rows."""
    both = key_width + value_width
    flops = 2.0 * both * heads * pairs * layers
    nbytes = bytes_per_el * layers * (kv_heads * both * kv_rows
                                      + heads * both * rows)
    return flops, float(nbytes)


def step_cost(steps, alloc: dict, heads: int, window_layers: int,
              full_layers: int) -> tuple[float, float]:
    """(FLOPs, bytes) of every attention read of ``steps`` (the ``args``
    of ``v2.schedule`` spans of such a model: ``full_qk_pairs``,
    ``window_qk_pairs``, ``full_kv_rows``, ``window_kv_rows``,
    ``tokens``), both kinds, with the shapes ``alloc`` gives (the ``args``
    of the ``v2.state_alloc`` span)."""
    flops = nbytes = 0.0
    for kind, layers in (("full", full_layers), ("window", window_layers)):
        fl, by = read_cost(
            sum(a[f"{kind}_qk_pairs"] for a in steps),
            sum(a[f"{kind}_kv_rows"] for a in steps),
            sum(a["tokens"] for a in steps), layers, heads,
            alloc[f"{kind}_kv_heads"], alloc["key_width"],
            alloc["value_width"])
        flops, nbytes = flops + fl, nbytes + by
    return flops, nbytes
