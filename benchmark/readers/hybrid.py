"""Per-layer metrics of a one-mixer-a-layer model (a Mamba-2 scan, an
attention layer or held experts a layer): the ``ssd_ragged`` kernel in the
device trace, the ``ssm_*``, ``kv_pages_held`` and ``expert_rows``
arguments of the program's ``v2.schedule`` spans, and what its one
``v2.state_alloc`` span says of the layers, the slots, the pages and the
Pallas calls ONE step program makes.  On a program that has no such
argument (every other model; the parent commit) each reader finds nothing
and returns None.
"""

from __future__ import annotations

from benchmark.lib import ssm_cost
from benchmark.lib.peaks import peaks_for
from benchmark.lib.stats import percentile
from benchmark.readers import ssm


def _alloc(run):
    """``args`` of the ``v2.state_alloc`` span of a one-mixer-a-layer
    model, or None."""
    for e in run.spans:
        if e.get("ph") == "X" and e["name"] == "v2.state_alloc" \
                and "kernel_calls_per_step" in e["args"]:
            return e["args"]
    return None


def _schedules(run):
    """``args`` of the window's ``v2.schedule`` spans that say what the
    slots, the pages and the experts were asked."""
    lo, hi = run.counters["window_mono_us"]
    return [e["args"] for e in run.spans
            if e.get("ph") == "X" and e["name"] == "v2.schedule"
            and "kv_pages_held" in e["args"] and lo <= e["ts"] <= hi]


def ssd_scan_roofline(run, cell):
    """100 x the least time the chip could take for the scans of the
    traced steps, over the time the ``ssd_ragged`` kernel took in the
    trace (``ssm._kernel_s``: never less than it took).  WHICH steps were
    traced is not known, only how many: the trace's Pallas calls over the
    calls one step program makes, which the PROGRAM says
    (``kernel_calls_per_step`` of ``v2.state_alloc``).  The least work of
    that many consecutive steps, over every such run of steps scheduled
    around the traced stretch, is counted: no more than was traced."""
    alloc = _alloc(run)
    if alloc is None or not alloc["kernel_calls_per_step"]:
        return None
    s = ssm._kernel_s(run)
    if s is None:
        return None
    per_step = alloc["kernel_calls_per_step"]
    n = int(run.trace.mosaic_calls // per_step)
    lo, hi = run.counters["window_mono_us"]
    start = lo + max(0.0, (hi - lo) / 1e6 - ssm.CAPTURE_S) / 2 * 1e6
    steps = ssm._schedules(run, start - ssm.SLACK_S[0] * 1e6,
                           start + (ssm.CAPTURE_S + ssm.SLACK_S[1]) * 1e6)
    if n < 1 or len(steps) < n:
        return None
    m = run.counters["model"]
    peaks = peaks_for(run.counters["device_kind"])

    def least(group):
        fl, by = ssm_cost.ssd_cost(
            sum(a["ssm_rows"] for a in group),
            sum(a["state_bytes"] for a in group), alloc["ssm_layers"],
            m.ssm_heads, m.ssm_head_dim, m.ssm_state, m.ssm_groups)
        return ssm_cost.least_time(fl, by, peaks)

    least_s, bound = min(least(steps[i:i + n])
                         for i in range(len(steps) - n + 1))
    print(f"[ssd] {n} ragged steps in the trace ({run.trace.mosaic_calls:.0f} "
          f"Pallas calls, {per_step} a step by the program's own count), "
          f"{ssm.KERNEL} {s * 1e3:.2f} ms; the least work of {n} consecutive "
          f"steps of the {len(steps)} scheduled around the traced stretch "
          f"needs {least_s * 1e3:.3f} ms, bound by {bound}", flush=True)
    return 100.0 * least_s / s


def expert_rows_per_held_p50(run, cell):
    """Median over the window's steps of the rows a held expert is
    expected to get: ``expert_rows`` (rows x experts per token x held /
    routed, ONE expert layer's) over the experts held.  Beside the rows
    of a tile (128) it says how full a tile is."""
    held = getattr(run.counters["model"], "experts_held", 0)
    rows = [a["expert_rows"] / held for a in _schedules(run)
            if held and "expert_rows" in a]
    return percentile(rows, 0.5) if rows else None


def kv_cache_share_p50(run, cell):
    """Median over the window's steps of the pages' share of the
    per-sequence memory in use: ``kv_pages_held x page_bytes x
    attn_layers`` over that plus ``state_slots_live x slot_bytes``; the
    rest is recurrent state, and the larger of the two bounds admission."""
    alloc = _alloc(run)
    if alloc is None:
        return None
    shares = []
    for a in _schedules(run):
        pages = a["kv_pages_held"] * alloc["page_bytes"] * alloc["attn_layers"]
        slots = a["state_slots_live"] * alloc["slot_bytes"]
        if pages + slots > 0:
            shares.append(pages / (pages + slots))
    return percentile(shares, 0.5) if shares else None
