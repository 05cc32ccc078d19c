#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one engine, one
warm-up, then one window per offered rate, each behind a new server.

    chiprun -- python3 benchmark/tools/sweep_rate.py <workload> <seconds> <rate>...

Prints one row per rate (offered, completed, time to first token, token
gap, unfinished at the window's end) and writes them to
``chiprun_out/sweep/<workload>.json``.  The knee is read off the table by
hand and four fifths of it goes into the traffic file: the benchmark
itself never searches.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    workload, seconds = argv[0], float(argv[1])
    rates = [float(r) for r in argv[2:]]
    from benchmark.lib import device, serve_driver
    from benchmark.lib.manifest import load_cell
    from benchmark.lib.stats import percentile

    cell = load_cell(ROOT, workload)
    device.require_chips(cell.chips)
    device.setup_compile_cache()
    compiles = device.CompileCounter()
    eng, model, correct = serve_driver.build(cell, 1, compiles)
    rows = []
    for k, rate in enumerate(rates):
        cell.traffic["rate_per_s"] = rate
        run = serve_driver.serve(cell, eng, model, correct, 100 + k, seconds,
                                 False, time.perf_counter(), compiles)
        c = run.counters
        rows.append({
            "rate_offered": rate, "sent": run.attempted,
            "failed": run.failed,
            "tokens_per_s": run.end_to_end["serve_tokens_per_s"],
            "ttft_p50_ms": percentile(c["ttft_s"], 0.5) * 1e3,
            "ttft_p95_ms": run.end_to_end["ttft_p95_ms"],
            "gap_p50_ms": percentile(c["gap_s"], 0.5) * 1e3,
            "gap_p95_ms": run.end_to_end["token_gap_p95_ms"],
            "unfinished_at_end": c["backlog"],
            "compiles_in_window": c["compiles_in_window"]})
        print("SWEEP", json.dumps(rows[-1]), flush=True)
    out = ROOT / "chiprun_out" / "sweep"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
