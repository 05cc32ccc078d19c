#!/usr/bin/env python3
"""One run of a cell with a profiler capture of a stretch of the window,
kept until ``lib.stages`` has read it: the device's time by stage of the
model (the program's ``jax.named_scope`` names on the operations' name
stacks), for the stretch as a whole and for each compiled program, every
program labelled with what its runs carried (a decode step, a 1024-row
chunk).  The stretch is the middle of the window, as a traced run of
``benchmark/run.py`` takes it, and a serving mix makes its sequence of
requests for the window's length (``lib.traffic.serve_plan``): only
``--seconds`` of the benchmark's ``run_seconds`` replays the sequence,
and so runs the programs (the block buckets), that a ledger line's
table names.  The tool of PERF.md section 5's stage tables:

    chiprun -- python3 benchmark/tools/stage_run.py --workload <cell> \\
        --seed <n> --seconds <s>

Modelled on ``tools/attributed_run.py`` and built from its parts: the
cell runs through its own driver with ``traced=False`` and the program's
spans on, the capture starts mid-window between two clock anchors, and
``lib.attribute`` puts the ``v2.schedule`` spans on each device plane's
clock, which is how a program's runs get their ``tokens`` and
``prefill_tokens``.  ``benchmark/run.py`` cannot print this yet: its
``DeviceTracer`` deletes the capture once ``lib.trace`` has keyed it by
instruction name (``PERF.md`` section 7 (j) has the edits).  Last line: one
JSON object; ``breakdown.stages`` is ``lib.stages.stage_tables``.  On a
program without the scopes (the parent commit; a train cell, whose step
has no stage name yet) every operation reads ``unscoped``; on a machine without the chip (a test's rehearsal) the
capture holds no device plane and no device number is printed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                     # noqa: E402
import json                         # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def schedule_steps(events, to_device):
    """``(start on the device's clock, tokens, prompt tokens)`` of the
    ``v2.schedule`` spans; ``to_device``: Tracer ns -> device ns.  A
    self-drafting step's verify runs (a stream's token and its draft, two
    rows of a context that is there) count among its ``prefill_tokens``
    (``engine_v2.step_counts``: what adds more than one row is a chunk);
    they are taken out again, so that such a step reads as a decode
    step."""
    out = []
    for e in events:
        if e.get("ph") != "X" or e["name"] != "v2.schedule":
            continue
        a = e["args"]
        verify = int(a.get("verify_runs", 0)) + int(a.get("draft_rows", 0))
        out.append((to_device(e["ts"] * 1e3), int(a.get("tokens", 0)),
                    max(0, int(a.get("prefill_tokens", 0)) - verify)))
    return out


def steps_by_plane(capture, events, attribute):
    """The schedule spans on every device plane's clock that a bracket was
    found for (``lib.attribute``: anchors give Tracer <-> host plane, the
    runtime's enqueue and done events host <-> device plane)."""
    amap = attribute.anchor_map(capture.anchors)
    out, worst = {}, 0.0
    if amap is None:
        return out, None
    for p in capture.planes:
        ordinal = int(p.name.rsplit(":", 1)[1])
        bracket = attribute.device_offset(
            capture.runs.get(ordinal, []),
            [(t, rid) for t, rid, dev in capture.enqueues if dev == ordinal],
            [t for t, core in capture.dones if core == ordinal])
        if bracket is None:
            continue
        lo, hi = bracket
        d = (lo + hi) / 2
        worst = max(worst, (hi - lo) / 2)
        out[p.name] = schedule_steps(
            events, lambda mono, d=d: amap.to_host(mono) - d)
    return out, worst


def main(argv=None, root: Path = ROOT, need_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="",
                    help="write the JSON object to this file as well")
    args = ap.parse_args(argv)

    from benchmark.lib import attribute, device, harness, stages, trace
    from benchmark.lib.manifest import load_cell, load_code
    from benchmark.lib.profiler import DeviceTracer
    from benchmark.lib.run import log

    parts = load_code(root, "tools", "attributed_run")
    cell = load_cell(root, args.workload)
    if need_chip:       # a test's rehearsal on the CPU passes False
        try:
            device.require_chips(cell.chips)
        except device.NoAccelerator as e:
            print(f"stage_run: {e}", file=sys.stderr)
            return 1
    cache = device.setup_compile_cache()
    kind = cell.traffic["driver"]
    tracer = DeviceTracer(root, f"{cell.name}.stages")
    work = tracer.dir.parent / f"{cell.name}.stage_spans"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    spans_path = work / "spans.trace.json"
    tracing = {"enabled": True, "max_events": parts.MAX_EVENTS,
               "trace_path": str(spans_path)}
    if kind == "train_steps":
        cell.config["ds_config"] = dict(cell.config["ds_config"],
                                        telemetry={"tracing": tracing})
    else:
        cell.config["server_config"] = dict(
            cell.config.get("server_config", {}), tracing=tracing)
    span_s = min(parts.CAPTURE_S[kind], args.seconds / 3)
    names = stages.stage_names()
    log(f"[stages] {cell.name}: seed {args.seed}, {args.seconds:g} s, "
        f"{span_s:g} s captured, {len(names)} stage names; compile cache "
        f"{cache}")
    cap = parts.Capturer(device.CompileCounter(), tracer, args.seconds,
                         span_s, True)
    try:
        run = harness.DRIVERS[kind](cell, args.seed, args.seconds, False,
                                    T_START, cap)
    finally:
        if cap.thread is not None:
            cap.thread.join()

    events = []
    if spans_path.exists():
        events = [e for e in json.loads(spans_path.read_text())["traceEvents"]
                  if e.get("ph") in ("X", "i")]
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "device": device.facts(),
           "end_to_end": dict(run.end_to_end, setup_s=run.setup_s),
           "span_events": len(events), "stage_names": len(names)}
    pb = trace.find_xplane(str(tracer.dir)) if cap.capture_mono else None
    capture = attribute.load_capture(pb) if pb else None
    if capture is not None and capture.planes:  # none in a CPU rehearsal
        reduction = trace.reduce_planes(capture.planes)
        steps, err = steps_by_plane(capture, events, attribute)
        staged = stages.load_stage_capture(pb)
        tables = stages.stage_tables(staged, names, steps)
        log(f"[stages] {len(staged.programs)} programs in the capture, "
            f"{sum(len(p.ops) for p in staged.planes)} operations, "
            f"{sum(len(s) for s in steps.values())} schedule spans on the "
            f"device's clock (clock error "
            f"{'no bracket' if err is None else f'{err * 1e-6:.4f} ms'}); "
            f"unscoped {tables['unscoped_share'] * 100:.2f} %")
        out["device"].update(busy_s=reduction.busy_s,
                             window_s=reduction.window_s)
        out["clock_error_ms"] = None if err is None else err * 1e-6
        out["breakdown"] = {"stages": tables,
                            "device_ops": reduction.top_ops}
    shutil.rmtree(work, ignore_errors=True)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    if args.out:
        (root / args.out).parent.mkdir(parents=True, exist_ok=True)
        (root / args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
