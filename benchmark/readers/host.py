"""Per-layer metrics from the host's clock, the program's ``Tracer`` spans
and the benchmark's own counters.  A reader that finds nothing to read
returns None, and the harness leaves that metric out of the line."""

from __future__ import annotations

from benchmark.lib.stats import percentile


def _spans(run, name):
    """The program's spans of one name that began inside the window."""
    lo, hi = run.counters["window_mono_us"]
    return [ev for ev in run.spans
            if ev.get("ph") == "X" and ev["name"] == name
            and lo <= ev["ts"] <= hi]


def _p50_ms(run, name):
    spans = _spans(run, name)
    return percentile([ev["dur"] / 1e3 for ev in spans], 0.5) \
        if spans else None


def step_ms_p50(run, cell):
    """Median host time of one ``train_batch`` to ``block_until_ready``,
    over the window's untraced steps."""
    steps = run.counters.get("step_s")
    return percentile(steps, 0.5) * 1e3 if steps else None


def queue_wait_p50_ms(run, cell):
    return _p50_ms(run, "serve.queue_wait")


def serve_step_ms_p50(run, cell):
    return _p50_ms(run, "serve.step")


def prefill_tokens_per_s(run, cell):
    """Prompt tokens over the summed time requests spent between
    admission and their first token (``serve.prefill`` spans)."""
    spans = _spans(run, "serve.prefill")
    busy_s = sum(ev["dur"] for ev in spans) / 1e6
    tokens = sum(ev["args"].get("tokens", 0) for ev in spans)
    return tokens / busy_s if busy_s > 0 and tokens else None


def compiles_in_window(run, cell):
    n = run.counters.get("compiles_in_window")
    return None if n is None else float(n)


def loadgen_late_p95_ms(run, cell):
    late = run.counters.get("late_s")
    return percentile(late, 0.95) * 1e3 if late else None


def ttft_p95_ms(run, cell):
    """The tail of the waits for a first token, every request counted:
    per layer in a cell whose tail swings too far from run to run to
    carry a bound."""
    ttft = run.counters.get("ttft_s")
    return percentile(ttft, 0.95) * 1e3 if ttft else None


def token_gap_p95_ms(run, cell):
    gaps = run.counters.get("gap_s")
    return percentile(gaps, 0.95) * 1e3 if gaps else None
