"""Per-layer metrics of a self-drafting ragged step (a model that drafts
through its own multi-token-prediction module): the ``drafts`` and
``accepted`` arguments of the program's ``v2.fetch`` spans and the
``verify_runs`` argument of its ``v2.schedule`` spans.  On a program
that has no such argument (every engine that does not draft for itself;
the parent commit) each reader finds nothing and returns None."""

from __future__ import annotations

from benchmark.lib.stats import percentile


def _args(run, name: str, key: str):
    """``args`` of the window's ``name`` spans that carry ``key``."""
    lo, hi = run.counters["window_mono_us"]
    return [e["args"] for e in run.spans
            if e.get("ph") == "X" and e["name"] == name
            and key in e.get("args", {}) and lo <= e["ts"] <= hi]


def draft_accept_share(run, cell):
    """Drafts accepted over drafts verified, over the window: the
    ``accepted`` and ``drafts`` counts every self-drafting step's ONE
    fetch brings back (a draft is the module's argmax, accepted where it
    equals the main model's)."""
    fetched = _args(run, "v2.fetch", "drafts")
    drafts = sum(a["drafts"] for a in fetched)
    if drafts <= 0:
        return None
    return sum(a["accepted"] for a in fetched) / drafts


def verify_runs_p50(run, cell):
    """Median over the window's steps of the sequences that brought a
    verify run (their pending token and a draft, two rows)."""
    runs = [a["verify_runs"] for a in _args(run, "v2.schedule",
                                            "verify_runs")]
    return percentile(runs, 0.5) if runs else None
