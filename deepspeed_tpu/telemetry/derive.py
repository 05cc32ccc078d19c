"""Shared derivation math: one home for the numbers everybody re-derives.

``bench.py`` rows, the capture trigger, and the run ledger all compute
the same four things — percentiles, model FLOPs/token, MFU, and the
"p95 vs trailing median" regression heuristic.  Before this module each
had its own copy, which is exactly how row math and ledger math drift
apart.  Now there is ONE implementation:

* :func:`percentile` — the repo-frozen index formula
  ``xs[min(len-1, int(q*(len-1)))]`` on a sorted copy (matches the
  registry Histogram and every inline bench closure, so a ledger p95
  equals the row's p95 bit-for-bit).
* :func:`fwd_flops_per_tok` / :func:`mfu` — GQA-aware analytic model
  FLOPs and the fwd+bwd MFU against a peak (bench.py's row math; the
  ledger re-derives MFU from rollup inputs through the same code).
* :func:`trailing_regressed` — the capture-trigger heuristic
  (``p95 > factor × median`` over a trailing window,
  ``telemetry.capture`` delegates here) and :func:`step_time_spikes`,
  its per-step form used by the ledger's anomaly scan.

Pure stdlib, no jax — telemetry/ stays importable on a machine with no
device.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

#: bf16 matmul peak of the v5e chip the bench rows quote MFU against
V5E_PEAK_FLOPS_PER_SEC = 197e12

#: fwd+bwd FLOPs multiple of the forward pass (the standard 3x)
FWD_BWD_FACTOR = 3


def percentile(xs: Sequence[float], q: float) -> float:
    """Frozen repo percentile: sorted ``xs[min(len-1, int(q*(len-1)))]``.

    ``q`` is a fraction in [0, 1].  Empty input returns 0.0 — callers
    treat "no samples" as "no signal", not an error.
    """
    if not xs:
        return 0.0
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(q * (len(ys) - 1)))]


def p50(xs: Sequence[float]) -> float:
    return percentile(xs, 0.50)


def p95(xs: Sequence[float]) -> float:
    return percentile(xs, 0.95)


def p99(xs: Sequence[float]) -> float:
    return percentile(xs, 0.99)


def fwd_flops_per_tok(model, seq: int) -> float:
    """Model fwd FLOPs/token: qkvo (GQA-aware) + ffn + lm_head + attn.

    ``model`` is anything with ``hidden_size`` / ``num_layers`` /
    ``vocab_size`` (ModelConfig or a duck-typed stand-in); optional
    ``intermediate_size`` / ``activation`` / ``num_heads`` /
    ``num_kv_heads`` refine the ffn and GQA terms.
    """
    h, L, V = model.hidden_size, model.num_layers, model.vocab_size
    ffn = getattr(model, "intermediate_size", 4 * h)
    act = 3 if getattr(model, "activation", "gelu") == "swiglu" else 2
    heads = getattr(model, "num_heads", 1)
    kv_heads = getattr(model, "num_kv_heads", None) or heads
    qkvo = 2 * h * h + 2 * h * (h * kv_heads // heads)  # q,o + k,v (GQA)
    matmul = L * (qkvo + act * h * ffn)
    return 2 * matmul + 2 * h * V + 2 * seq * h * L


def mfu(tokens_per_sec: float, model, seq: int,
        peak_flops_per_sec: float = V5E_PEAK_FLOPS_PER_SEC) -> float:
    """fwd+bwd model-FLOP utilisation of ``peak_flops_per_sec``."""
    if peak_flops_per_sec <= 0:
        return 0.0
    return (tokens_per_sec * FWD_BWD_FACTOR * fwd_flops_per_tok(model, seq)
            / peak_flops_per_sec)


def trailing_regressed(times: Sequence[float], factor: float,
                       min_samples: int = 8) -> bool:
    """The capture-trigger heuristic: windowed ``p95 > factor × median``.

    ``times`` is the trailing window of step wall-times (the capture
    controller feeds its deque).  Fewer than ``min_samples`` samples or
    a non-positive factor never trigger.
    """
    if factor <= 0 or len(times) < min_samples:
        return False
    xs = sorted(times)
    median = xs[len(xs) // 2]
    p95_ = xs[min(len(xs) - 1, int(0.95 * (len(xs) - 1)))]
    return median > 0 and p95_ > factor * median


def step_time_spikes(times: Sequence[float], factor: float,
                     window: int = 32, min_samples: int = 8
                     ) -> List[Tuple[int, float, float]]:
    """Per-step form of the capture trigger for the ledger anomaly scan.

    Walks the series with a trailing window of up to ``window`` PRIOR
    samples; index ``i`` spikes when ``times[i] > factor × median`` of
    its window (≥ ``min_samples`` priors).  Returns
    ``[(index, value, threshold), ...]``.
    """
    out: List[Tuple[int, float, float]] = []
    if factor <= 0:
        return out
    for i in range(len(times)):
        lo = max(0, i - window)
        prior = sorted(times[lo:i])
        if len(prior) < min_samples:
            continue
        median = prior[len(prior) // 2]
        threshold = factor * median
        if median > 0 and times[i] > threshold:
            out.append((i, times[i], threshold))
    return out


def value_cliffs(values: Sequence[Optional[float]], ratio: float,
                 window: int = 32, min_samples: int = 8
                 ) -> List[Tuple[int, float, float]]:
    """Trailing-median CLIFF detector (the spike dual, for MFU): index
    ``i`` is a cliff when ``values[i] < ratio × median`` of its trailing
    window.  None entries are skipped (rows without the signal)."""
    out: List[Tuple[int, float, float]] = []
    if ratio <= 0:
        return out
    series = [(i, v) for i, v in enumerate(values) if v is not None]
    for j, (i, v) in enumerate(series):
        prior = sorted(x for _, x in series[max(0, j - window):j])
        if len(prior) < min_samples:
            continue
        median = prior[len(prior) // 2]
        threshold = ratio * median
        if median > 0 and v < threshold:
            out.append((i, v, threshold))
    return out
