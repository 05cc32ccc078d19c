"""Per-layer metrics of a model that mixes window and full attention by
layer, whose window layers keep their rows in a page pool of their own
that frees pages behind the window: the ``full_kv_rows`` /
``window_kv_rows``, ``full_pages`` / ``window_pages`` and ``pages_freed``
arguments of the program's ``v2.schedule`` spans (each for ONE layer of
its kind).  On a program that has no such argument (every other model;
the parent commit) each reader finds nothing and returns None.
"""

from __future__ import annotations

from benchmark.lib.stats import percentile


def _schedules(run):
    """``args`` of the window's ``v2.schedule`` spans that say what the
    two pools hold."""
    lo, hi = run.counters["window_mono_us"]
    return [e["args"] for e in run.spans
            if e.get("ph") == "X" and e["name"] == "v2.schedule"
            and "window_pages" in e["args"] and lo <= e["ts"] <= hi]


def window_read_share_p50(run, cell):
    """Median over the window's steps of the keys a window layer reads
    (``window_kv_rows``: each sequence's context once, cut to the window)
    over the keys a full layer reads for the same rows
    (``full_kv_rows``): 1 while no context is past the window."""
    shares = [a["window_kv_rows"] / a["full_kv_rows"]
              for a in _schedules(run) if a["full_kv_rows"] > 0]
    return percentile(shares, 0.5) if shares else None


def kv_held_share_p50(run, cell):
    """Median over the window's steps of the pages the two pools hold for
    the live sequences, every layer's, over what ONE undivided block
    table would hold for them (every layer a full layer's pages): with w
    window layers of L, ``((L - w) full_pages + w window_pages) / (L
    full_pages)``."""
    m = run.counters["model"]
    layers, win = m.num_layers, getattr(m, "window_layers", 0)
    shares = [((layers - win) * a["full_pages"] + win * a["window_pages"])
              / (layers * a["full_pages"])
              for a in _schedules(run) if a["full_pages"] > 0]
    return percentile(shares, 0.5) if shares else None


def pages_freed_per_s(run, cell):
    """Window pages the steps begun in the window returned to their free
    list, per second of the window; 0 says the mechanism did not run."""
    steps = _schedules(run)
    if not steps:
        return None
    return sum(a["pages_freed"] for a in steps) / run.counters["window_s"]
