"""Per-layer metrics from the program's own host spans (ISSUE 26) and from
``lib.attribute``, which puts them on the device trace's clock.

``run.spans`` holds the program's ``Tracer`` events; ``run.attribution``
a ``lib.attribute.Attribution`` of the traced stretch.  The harness's
drivers do not make an attribution (``lib/profiler.py`` deletes the capture
once reduced), so through ``benchmark/run.py`` the readers that need one
find nothing and return None, as every reader does on a program that has
no such span (the parent commit); ``tools/attributed_run.py`` makes one
and reads all of them.
"""

from __future__ import annotations

from benchmark.lib import flops
from benchmark.lib.peaks import peaks_for
from benchmark.lib.stats import percentile


def _window(run):
    lo, hi = run.counters["window_mono_us"]
    skip = run.counters.get("capture_mono_us")      # a traced stretch
    return [e for e in run.spans
            if e.get("ph") == "X" and lo <= e["ts"] <= hi
            and not (skip and e["ts"] < skip[1]
                     and e["ts"] + e["dur"] > skip[0])]


def train_step_span_ms_p50(run, cell):
    """Median ``train.step`` span of the window's untraced steps:
    ``step_ms_p50.train`` from inside the program, up to the
    ``block_until_ready`` the benchmark adds after it."""
    steps = [e["dur"] / 1e3 for e in _window(run)
             if e["name"] == "train.step"]
    return percentile(steps, 0.5) if steps else None


def serve_host_ms(events):
    """Per serve-loop iteration that ran a step, the host's own time in
    ms: ``serve.admit_pass`` + ``serve.step`` without the ``v2.fetch``
    inside it + ``serve.deliver``.  That is ``serve.admit_pass`` +
    ``serve.deliver`` + ``v2.schedule`` + ``v2.h2d`` + ``v2.dispatch`` +
    the self time of ``serve.step`` and ``v2.ragged_step``: the iteration
    less the wait for the device."""
    out, admit, step = [], 0.0, None
    for e in sorted(events, key=lambda e: e["ts"]):
        name = e["name"]
        if name == "serve.admit_pass":
            admit = e["dur"]
        elif name == "serve.step":
            step = [admit + e["dur"], e["ts"] + e["dur"]]
        elif name == "v2.fetch" and step and e["ts"] < step[1]:
            step[0] -= e["dur"]
        elif name == "serve.deliver" and step:
            out.append((step[0] + e["dur"]) / 1e3)
            step = None
    return out


def serve_host_ms_p50(run, cell):
    host = serve_host_ms(_window(run))
    return percentile(host, 0.5) if host else None


def idle_attributed_share(run, cell):
    """100 x idle time under a named program span over the idle time of
    the gaps longer than the clock error, traced stretch."""
    a = getattr(run, "attribution", None)
    if a is None or a.clock_error_s is None or a.long_idle_s <= 0:
        return None
    return 100.0 * a.named_s / a.long_idle_s


def paged_least_time(steps, model, peaks, bytes_per_el=2):
    """``(seconds, "compute" | "memory")``: the least time the chip could
    take for the attention of ragged steps with the given ``(qk_pairs,
    kv_rows, tokens)``, every layer: the larger of FLOPs over peak FLOP/s
    and bytes over peak bytes/s.  QK^T and PV are ``4 * head_dim`` FLOPs
    a live pair and query head (``flops.paged_decode_cost`` at one
    token); each sequence's keys and values are read once a step,
    however many of its tokens the step holds, and each token's query
    and output rows once."""
    def cost(n):        # (FLOPs, bytes) of one token over n rows
        return flops.paged_decode_cost([n], model.num_heads, model.kv_heads,
                                       model.dim_per_head,
                                       bytes_per_el=bytes_per_el)
    qo_bytes = cost(0)[1]
    fl = sum(cost(pairs)[0] for pairs, _, _ in steps)
    by = sum(cost(rows)[1] + (tokens - 1) * qo_bytes
             for _, rows, tokens in steps)
    t_f = model.num_layers * fl / peaks["flops_per_s_bf16"]
    t_b = model.num_layers * by / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), "compute" if t_f >= t_b else "memory"


def paged_roofline(run, cell):
    """100 x least time for the attention of the ragged steps whose
    ``v2.ragged_step`` span lies wholly inside the traced stretch, over
    the time of the ``paged_*`` kernels inside those spans."""
    a = getattr(run, "attribution", None)
    if a is None or a.clock_error_s is None or not a.stretch_mono_us:
        return None
    lo, hi = a.stretch_mono_us
    spans = [e for e in run.spans if e.get("ph") == "X"]
    steps = sorted(((e["ts"], e["ts"] + e["dur"]) for e in spans
                    if e["name"] == "v2.ragged_step" and e["ts"] >= lo
                    and e["ts"] + e["dur"] <= hi))
    if not steps:
        return None
    counts = [(e["args"]["qk_pairs"], e["args"]["kv_rows"],
               e["args"]["tokens"]) for e in spans
              if e["name"] == "v2.schedule" and "qk_pairs" in e["args"]
              and any(s <= e["ts"] <= t for s, t in steps)]
    paged_us = sum(t1 - t0 for name, t0, t1 in a.pallas_events
                   if name.startswith("paged_")
                   and any(s <= t0 and t1 <= t for s, t in steps))
    if not counts or paged_us <= 0:
        return None
    least_s, bound = paged_least_time(
        counts, run.counters["model"],
        peaks_for(run.counters["device_kind"]))
    print(f"[paged] {len(counts)} ragged steps wholly inside the traced "
          f"stretch, paged kernels {paged_us / 1e3:.2f} ms; least time "
          f"{least_s * 1e3:.3f} ms, bound by {bound}", flush=True)
    return 100.0 * least_s / (paged_us / 1e6)
