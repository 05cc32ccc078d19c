"""Per-layer metrics of a latent-attention model with a learned indexer:
the ``latent_index_scores`` kernel in the device trace, and the
``index_pairs`` / ``selected_keys`` arguments of the program's
``v2.schedule`` spans.  On a program that has no such kernel or
no such argument (every other model; the parent commit) each reader finds
nothing and returns None.

What the trace cannot name: the selection (counting passes over the
scores), the gather of the selected rows and their attention, the window
layers' reads and the expert products are XLA fusions, which the trace's
table lists by number (``fusion.654``), the same number meaning another
operation in another bucket's program.  No reader counts them.
"""

from __future__ import annotations

from benchmark.lib import latent_cost
from benchmark.lib.peaks import peaks_for
from benchmark.lib.stats import percentile

KERNEL = "latent_index_scores"
# a traced run starts its capture this long into the window's middle
# stretch at the earliest, and stops it this much later at the latest
CAPTURE_S = 5.0
SLACK_S = (1.0, 5.0)


def _kernel_s(run):
    """Seconds a chip spent in the kernel, or None where the run has no
    such kernel: no Pallas time in the trace, or no ``v2.schedule`` span
    that says what an indexer was asked (another model's program, the
    parent commit's).  The kernel is a fiftieth of the chip's time here
    and seldom among the ten operations the trace's table names, so its
    time is taken as ALL Pallas time less what the table gives to other
    kernels by name: never less than the kernel took, so a share of the
    roofline never reads too high."""
    t = run.trace
    if t is None or t.mosaic_s <= 0 or not _schedules(run):
        return None
    return t.mosaic_s - sum(s for name, s in t.top_ops
                            if name.endswith(" pallas")
                            and not name.startswith(KERNEL))


def _schedules(run, lo=None, hi=None):
    """``args`` of the ``v2.schedule`` spans that say what the indexer was
    asked, in time order, begun inside ``[lo, hi]``."""
    return [e["args"] for e in sorted(run.spans, key=lambda e: e["ts"])
            if e.get("ph") == "X" and e["name"] == "v2.schedule"
            and "index_pairs" in e["args"]
            and (lo is None or lo <= e["ts"] <= hi)]


def _indexed_layers(model) -> int:
    return sum(1 for full, _ in model.mla.kinds(model.num_layers) if full)


def selected_share_p50(run, cell):
    """Median over the window's steps of the keys the full layers'
    attention reads (``selected_keys``: at most ``index_topk`` a row)
    over the keys causally visible to the same rows (``index_pairs``):
    what the selection saves, 1 where no context is past ``index_topk``."""
    shares = [a["selected_keys"] / a["index_pairs"]
              for a in _schedules(run, *run.counters["window_mono_us"])
              if a["index_pairs"] > 0]
    return percentile(shares, 0.5) if shares else None


def index_scores_roofline(run, cell):
    """100 x the least time the chip could take for the indexer's scores
    of the traced steps, over the time the ``latent_index_scores`` kernel
    took in the trace.  WHICH steps were traced is not known, only how
    many (the kernel runs once an indexed layer a step): the least work of
    that many consecutive steps, over every such run of steps scheduled
    around the traced stretch, is counted: no more than was traced."""
    s = _kernel_s(run)
    if s is None:
        return None
    m = run.counters["model"]
    layers = _indexed_layers(m)
    n = int(run.trace.mosaic_calls // layers)
    lo, hi = run.counters["window_mono_us"]
    start = lo + max(0.0, (hi - lo) / 1e6 - CAPTURE_S) / 2 * 1e6
    steps = _schedules(run, start - SLACK_S[0] * 1e6,
                       start + (CAPTURE_S + SLACK_S[1]) * 1e6)
    # fewer spans than traced steps (clocks apart): all of them, which
    # reads low, never high
    n = min(n, len(steps))
    if n < 1:
        return None
    peaks = peaks_for(run.counters["device_kind"])

    def least(group):
        fl, by = latent_cost.index_scores_cost(
            sum(a["index_pairs"] for a in group),
            sum(a["latent_rows"] for a in group),
            sum(a["kv_rows"] for a in group), layers, m.mla.index_heads,
            m.mla.index_head_dim)
        return latent_cost.least_time(fl, by, peaks)

    least_s, bound = min(least(steps[i:i + n])
                         for i in range(len(steps) - n + 1))
    print(f"[index] {n} ragged steps in the trace "
          f"({run.trace.mosaic_calls:.0f} Pallas calls, {layers} a step), "
          f"{KERNEL} {s * 1e3:.2f} ms; the least work of {n} consecutive "
          f"steps of the {len(steps)} scheduled around the traced stretch "
          f"needs {least_s * 1e3:.3f} ms, bound by {bound}", flush=True)
    return 100.0 * least_s / s
