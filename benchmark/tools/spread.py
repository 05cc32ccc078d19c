#!/usr/bin/env python3
"""Medians and spreads of the result lines a set of runs left behind.

    python3 benchmark/tools/spread.py chiprun_out/<tag> [chiprun_out/<tag2>]

Reads the last line of every ``*.out`` file in each directory (as
``benchmark/tools/run_cell.sh`` writes them), and prints for each metric
its values, its median and its spread (interquartile distance over the
median, ``benchmark/lib/stats.py``).  With two directories, the two sets'
spreads and medians stand side by side: the bound is about five times the
wider spread, and the second median may not be worse than the first by
more than the bound.  ``less one`` is the spread as the driver's check
reads it for tightness: without the run farthest from the median, where
that narrows it; the mean of the two sets' may be at most half the bound.
The first run of a set compiles, so ``setup_s`` is given with and without
it.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib.stats import spread  # noqa: E402


def load(directory: str) -> list[dict]:
    runs = []
    for path in sorted(Path(directory).glob("*.out"),
                       key=lambda p: p.stat().st_mtime):
        lines = path.read_text().strip().splitlines()
        if lines and lines[-1].startswith("{"):
            runs.append(json.loads(lines[-1]))
        else:
            print(f"{path}: no result line")
    return runs


def spread_less_one(xs) -> float:
    med = statistics.median(xs)
    far = max(range(len(xs)), key=lambda i: abs(xs[i] - med))
    return min(spread(xs), spread(xs[:far] + xs[far + 1:]))


def main(argv) -> int:
    for directory in argv:
        runs = load(directory)
        print(f"{directory}: {len(runs)} runs, correct "
              f"{[r['correct'] for r in runs]}, failed "
              f"{[r['failed'] for r in runs]}, peak bytes "
              f"{sorted({r['device']['memory_peak_bytes'] for r in runs})}")
        names = sorted({n for r in runs for n in r["metrics"]})
        for name in names:
            xs = [r["metrics"][name]["value"] for r in runs
                  if name in r["metrics"]]
            line = (f"  {name}: median {statistics.median(xs):.6g} "
                    f"min {min(xs):.6g} max {max(xs):.6g}")
            if len(xs) >= 2 and statistics.median(xs) != 0:
                line += f" spread {spread(xs):.4%}"
                if len(xs) >= 3:
                    line += f" less one {spread_less_one(xs):.4%}"
            if name == "setup_s" and len(xs) > 2:
                line += (f"; without the first run median "
                         f"{statistics.median(xs[1:]):.6g} first {xs[0]:.6g}")
            print(line)
            print("    " + " ".join(f"{x:.6g}" for x in xs))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
