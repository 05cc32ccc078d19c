"""Per-layer metrics from the profiler's device trace (``lib.trace``).
Each returns None when the run carries no device trace."""

from __future__ import annotations

from benchmark.lib import flops
from benchmark.lib.peaks import peaks_for


def device_idle_share(run, cell):
    """100 x (1 - time an operation ran / traced window), mean of chips."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def exposed_collective_share(run, cell):
    """100 x time a collective ran while no other operation did, over
    the traced window."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.exposed_collective_s / t.window_s


def pallas_time_share(run, cell):
    """100 x Pallas (Mosaic) kernel time over device busy time."""
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.mosaic_s / t.busy_s


def flash_roofline(run, cell):
    """100 x the least time the chip could take for the flash-attention
    forward and backward of the traced training steps, over the time its
    Pallas kernels took in the trace.  The least time is the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s; which of the two
    bounds it is printed.  A kernel run again for recomputation adds to
    the time and not to the FLOPs."""
    t = run.trace
    c = run.counters
    if t is None or t.mosaic_s <= 0 or not c.get("traced_steps"):
        return None
    m = c["model"]
    peaks = peaks_for(c["device_kind"])
    fl = by = 0.0
    for backward in (False, True):
        f, b = flops.flash_attention_cost(
            c["micro_batch"], m.num_heads, m.kv_heads, c["seq"], c["seq"],
            m.dim_per_head, causal=True, window=m.sliding_window or None,
            backward=backward)
        fl, by = fl + f, by + b
    calls = c["traced_steps"] * c["gas"] * m.num_layers
    t_flops = calls * fl / peaks["flops_per_s_bf16"]
    t_bytes = calls * by / peaks["hbm_bytes_per_s"]
    print(f"[flash] {calls} forward+backward sets per chip in the trace, "
          f"{t.mosaic_calls:.0f} Pallas calls, {t.mosaic_s * 1e3:.2f} ms; "
          f"least time {max(t_flops, t_bytes) * 1e3:.2f} ms, bound by "
          f"{'compute' if t_flops >= t_bytes else 'memory'}", flush=True)
    return 100.0 * max(t_flops, t_bytes) / t.mosaic_s
