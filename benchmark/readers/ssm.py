"""Per-layer metrics of the state-space mixer: the ``ssd_ragged`` kernel in
the device trace, and the ``ssm_*`` / ``state_*`` arguments of the
program's ``v2.schedule`` spans.  On a program that has no such kernel or
no such argument (every model without a mixer; the parent commit) each
reader finds nothing and returns None."""

from __future__ import annotations

from benchmark.lib import ssm_cost
from benchmark.lib.peaks import peaks_for
from benchmark.lib.stats import percentile

KERNEL = "ssd_ragged"
# a traced run starts its capture this long into the window's middle
# stretch at the earliest, and stops it this much later at the latest
CAPTURE_S = 5.0
SLACK_S = (1.0, 5.0)


def _kernel_s(run):
    """Seconds a chip spent in the kernel, or None where the trace's
    table of its ten largest operations (``<instruction> pallas``, the
    instruction the kernel's name and a number) shows no such kernel.
    Programs of different buckets can number the instruction
    differently, and the smaller of them fall off the table, so the
    kernel's time is taken as ALL Pallas time less what the table gives
    to other kernels by name: never less than the kernel took, so a
    share of the roofline never reads too high."""
    t = run.trace
    if t is None:
        return None
    pallas = [(name, s) for name, s in t.top_ops if name.endswith(" pallas")]
    if not any(name.startswith(KERNEL) for name, _ in pallas):
        return None
    return t.mosaic_s - sum(s for name, s in pallas
                            if not name.startswith(KERNEL))


def _schedules(run, lo=None, hi=None):
    """``args`` of the ``v2.schedule`` spans that say what the mixer was
    asked, in time order, begun inside ``[lo, hi]``."""
    return [e["args"] for e in sorted(run.spans, key=lambda e: e["ts"])
            if e.get("ph") == "X" and e["name"] == "v2.schedule"
            and "state_bytes" in e["args"]
            and (lo is None or lo <= e["ts"] <= hi)]


def ssm_time_share(run, cell):
    """100 x time of the ``ssd_ragged`` events (``_kernel_s``: at most
    this) over device busy time."""
    s = _kernel_s(run)
    if s is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * s / run.trace.busy_s


def state_slots_live_p50(run, cell):
    """Median ``state_slots_live`` of the window's ``v2.schedule`` spans:
    sequences that held a state slot when a step was scheduled."""
    live = [a["state_slots_live"]
            for a in _schedules(run, *run.counters["window_mono_us"])]
    return percentile(live, 0.5) if live else None


def ssd_roofline(run, cell):
    """100 x the least time the chip could take for the scans of the
    traced steps, over the time the ``ssd_ragged`` kernel took in the
    trace.  The device's clock is not the host's, so WHICH steps were
    traced is not known, only how many: every traced step runs the kernel
    once a layer, and the paged attention kernel as often where the
    configuration names ``paged_pallas``.  The least work of that many
    consecutive steps, over every such run of steps scheduled around the
    traced stretch, is counted: no more than was traced."""
    s = _kernel_s(run)
    if s is None:
        return None
    m = run.counters["model"]
    per_step = m.num_layers * (
        2 if cell.config.get("attention_impl") == "paged_pallas" else 1)
    n = int(run.trace.mosaic_calls // per_step)
    lo, hi = run.counters["window_mono_us"]
    start = lo + max(0.0, (hi - lo) / 1e6 - CAPTURE_S) / 2 * 1e6
    steps = _schedules(run, start - SLACK_S[0] * 1e6,
                       start + (CAPTURE_S + SLACK_S[1]) * 1e6)
    if n < 1 or len(steps) < n:
        return None
    peaks = peaks_for(run.counters["device_kind"])

    def least(group):
        fl, by = ssm_cost.ssd_cost(
            sum(a["ssm_rows"] for a in group),
            sum(a["state_bytes"] for a in group), m.num_layers, m.ssm_heads,
            m.ssm_head_dim, m.ssm_state, m.ssm_groups)
        return ssm_cost.least_time(fl, by, peaks)

    least_s, bound = min(least(steps[i:i + n])
                         for i in range(len(steps) - n + 1))
    print(f"[ssd] {n} ragged steps in the trace ({run.trace.mosaic_calls:.0f} "
          f"Pallas calls, {per_step} a step), {KERNEL} {s * 1e3:.2f} ms; the "
          f"least work of {n} consecutive steps of the {len(steps)} "
          f"scheduled around the traced stretch needs {least_s * 1e3:.3f} "
          f"ms, bound by {bound}", flush=True)
    return 100.0 * least_s / s
