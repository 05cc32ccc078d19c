"""Per-layer metrics of a mixed-attention model whose kinds of layer
differ in shape (window and full layers with KV heads of their own, keys
wider than values, a sink in the window layers' softmax): the ``paged_*``
kernels in the device trace, the ``full_qk_pairs`` / ``window_qk_pairs``,
``full_kv_rows`` / ``window_kv_rows``, ``full_pages`` /
``window_pages`` and ``expert_rows`` arguments of the program's
``v2.schedule`` spans (each for ONE layer of its kind), and what its one ``v2.state_alloc`` span says
of both kinds' pages, heads and widths and of the Pallas calls ONE step
program makes.  On a program that has no such argument (every other
model; the parent commit) each reader finds nothing and returns None.
"""

from __future__ import annotations

from benchmark.lib import mixed_cost
from benchmark.lib.peaks import peaks_for
from benchmark.lib.ssm_cost import least_time
from benchmark.lib.stats import percentile
from benchmark.readers.ssm import CAPTURE_S, SLACK_S

KERNEL = "paged_"


def _alloc(run):
    """``args`` of the ``v2.state_alloc`` span of such a model, or None."""
    for e in run.spans:
        if e.get("ph") == "X" and e["name"] == "v2.state_alloc" \
                and "window_page_bytes" in e["args"]:
            return e["args"]
    return None


def _schedules(run, lo, hi):
    """``args`` of the ``v2.schedule`` spans that count both kinds'
    pairs, in time order, begun inside ``[lo, hi]``."""
    return [e["args"] for e in sorted(run.spans, key=lambda e: e["ts"])
            if e.get("ph") == "X" and e["name"] == "v2.schedule"
            and "window_qk_pairs" in e["args"] and lo <= e["ts"] <= hi]


def _kernel_s(run):
    """Seconds a chip spent in the ``paged_*`` kernels, or None where the
    trace's table of its ten largest operations (``<instruction> pallas``,
    the instruction the kernel's name and a number) shows none.  Programs
    of different buckets number the instruction differently and the
    smaller fall off the table, so the time is taken as ALL Pallas time
    less what the table gives to other kernels by name (the append):
    never less than the reads took, so the share never reads too high."""
    t = run.trace
    if t is None:
        return None
    pallas = [(name, s) for name, s in t.top_ops if name.endswith(" pallas")]
    if not any(name.startswith(KERNEL) for name, _ in pallas):
        return None
    return t.mosaic_s - sum(s for name, s in pallas
                            if not name.startswith(KERNEL))


def paged_read_roofline(run, cell):
    """100 x the least time the chip could take for the attention reads
    of the traced steps, over the time the ``paged_*`` kernels took in the
    trace.  WHICH steps were traced is not known, only how many: the
    trace's Pallas calls over the calls one step program makes, which the
    PROGRAM says (``kernel_calls_per_step`` of ``v2.state_alloc``).  The
    least work of that many consecutive steps, over every such run of
    steps scheduled around the traced stretch, is counted: no more than
    was traced."""
    alloc = _alloc(run)
    if alloc is None or not alloc["kernel_calls_per_step"]:
        return None
    s = _kernel_s(run)
    if s is None:
        return None
    per_step = alloc["kernel_calls_per_step"]
    n = int(run.trace.mosaic_calls // per_step)
    lo, hi = run.counters["window_mono_us"]
    start = lo + max(0.0, (hi - lo) / 1e6 - CAPTURE_S) / 2 * 1e6
    steps = _schedules(run, start - SLACK_S[0] * 1e6,
                       start + (CAPTURE_S + SLACK_S[1]) * 1e6)
    if n < 1 or len(steps) < n:
        return None
    m = run.counters["model"]
    peaks = peaks_for(run.counters["device_kind"])
    win = alloc["window_layers"]

    def least(group):
        return least_time(*mixed_cost.step_cost(
            group, alloc, m.num_heads, win, m.num_layers - win), peaks)

    least_s, bound = min(least(steps[i:i + n])
                         for i in range(len(steps) - n + 1))
    print(f"[paged] {n} ragged steps in the trace "
          f"({run.trace.mosaic_calls:.0f} Pallas calls, {per_step} a step by "
          f"the program's own count), {KERNEL}* {s * 1e3:.2f} ms; the least "
          f"work of {n} consecutive steps of the {len(steps)} scheduled "
          f"around the traced stretch needs {least_s * 1e3:.3f} ms, bound "
          f"by {bound}", flush=True)
    return 100.0 * least_s / s


def kv_bytes_held_share_p50(run, cell):
    """Median over the window's steps of the BYTES the two pools hold for
    the live sequences, every layer's, over what ONE undivided table of
    as many window-kind layers would hold for them: with w window layers
    of L, ``((L - w) full_pages full_page_bytes + w window_pages
    window_page_bytes) / (L full_pages window_page_bytes)``; a page's
    bytes are one layer's of its kind, K and V, as laid out."""
    alloc = _alloc(run)
    if alloc is None:
        return None
    layers = run.counters["model"].num_layers
    win = alloc["window_layers"]
    shares = [((layers - win) * a["full_pages"] * alloc["full_page_bytes"]
               + win * a["window_pages"] * alloc["window_page_bytes"])
              / (layers * a["full_pages"] * alloc["window_page_bytes"])
              for a in _schedules(run, *run.counters["window_mono_us"])
              if a["full_pages"] > 0]
    return percentile(shares, 0.5) if shares else None


def held_expert_rows_p50(run, cell):
    """Median over the window's steps of the rows ONE held expert is
    expected to get from one expert layer: ``expert_rows`` (rows x experts
    per token x held / routed) over the experts held.  Under one row a
    step, an expert's three matrices are streamed for a single product
    (or not at all); beside the 128 rows of a tile it says how full a
    tile is.  Read off the spans that count both kinds' pairs, so only a
    program with such layers reports it."""
    held = getattr(run.counters["model"], "experts_held", 0)
    rows = [a["expert_rows"] / held
            for a in _schedules(run, *run.counters["window_mono_us"])
            if held and "expert_rows" in a]
    return percentile(rows, 0.5) if rows else None
