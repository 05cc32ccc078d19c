#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no child, nothing that outlives it.  Loads the cell's
configuration, makes weights and inputs from ``--seed``, checks the
program's output against the plain reference, warms the cell's own
shapes, measures for ``--seconds`` and prints, last, one JSON object.
Without a TPU, with another number of chips than the cell asks for, or
with a device that is not in ``benchmark/lib/peaks.py``, it exits 1 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up runs from here

import argparse                     # noqa: E402
import json                         # noqa: E402
import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.lib import device, harness
    from benchmark.lib.manifest import load_manifest

    cells = {w["name"]: w for w in load_manifest(ROOT)["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 1
    try:
        device.require_chips(int(cells[args.workload]["chips"]))
    except device.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
