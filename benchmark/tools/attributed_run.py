#!/usr/bin/env python3
"""One run of a cell with the program's spans on (or off) and, if asked,
a profiler capture of a stretch of the window that carries clock anchors
and is KEPT, so that ``lib.attribute`` can put the device's idle gaps down
to the host span that covers them and the Pallas seconds to kernels by
name.  The tool of ISSUE 26's measurements:

    chiprun -- python3 benchmark/tools/attributed_run.py --workload <cell> \\
        --seed <n> --seconds <s> --spans <0|1> --capture <0|1>

``--spans 1 --capture 0`` against ``--spans 0 --capture 0`` on the same
seed is what tracing costs when on; ``--capture 1`` gives the attribution
and the span metrics of ``readers/spans.py``.  It runs the cell through
its own driver with ``traced=False`` (the harness's ``DeviceTracer``
deletes its capture once reduced and writes no anchor; ``PERF.md`` section
7 has the edits that would let ``benchmark/run.py`` do this itself), with
the spans switched on in the cell's configuration in memory
(``server_config.tracing`` / ``ds_config.telemetry.tracing``), and starts
its own capture when the driver marks the window's start.  Last line: one
JSON object.  Works on a program without the spans (the parent commit):
what cannot be read is left out.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                     # noqa: E402
import json                         # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
import threading                    # noqa: E402
from pathlib import Path            # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CAPTURE_S = {"train_steps": 2.5, "open_loop": 5.0, "closed_loop": 5.0}
MAX_EVENTS = 4_000_000


def write_anchor(label: str) -> None:
    """The program's anchor; a program without one (the parent) gets none
    and its gaps keep their old names."""
    try:
        from deepspeed_tpu.utils.trace import write_clock_anchor
    except ImportError:
        return
    write_clock_anchor(label)


class Capturer:
    """Passed to the driver as its compile counter: ``mark()`` is the
    driver saying the window starts now.  From then, on a thread of its
    own, waits to mid-window and captures ``span_s`` seconds between two
    anchors with the harness's ``DeviceTracer``, whose ``reduce()`` (which
    deletes the capture) is never called."""

    def __init__(self, counter, tracer, seconds: float, span_s: float,
                 capture: bool):
        self.counter, self.tracer = counter, tracer
        self.seconds, self.span_s, self.capture = seconds, span_s, capture
        self.mark_mono = None
        self.capture_mono = None
        self.thread = None

    def __getattr__(self, name):
        return getattr(self.counter, name)

    def mark(self) -> None:
        self.counter.mark()
        self.mark_mono = time.monotonic()
        if self.capture:
            self.thread = threading.Thread(target=self._capture,
                                           name="bench-capture")
            self.thread.start()

    def _capture(self) -> None:
        time.sleep(max(0.0, (self.seconds - self.span_s) / 2))
        self.tracer.start()
        t0 = time.monotonic()
        write_anchor("start")
        time.sleep(self.span_s)
        write_anchor("stop")
        t1 = time.monotonic()
        self.tracer.stop()
        self.capture_mono = (t0, t1)


def main(argv=None, root: Path = ROOT, need_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--capture", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", default="",
                    help="copy the capture and the spans to this directory")
    args = ap.parse_args(argv)

    from benchmark.lib import attribute, device, harness, trace
    from benchmark.lib.manifest import load_cell, load_code
    from benchmark.lib.profiler import DeviceTracer
    from benchmark.lib.run import log

    cell = load_cell(root, args.workload)
    if need_chip:       # a test's rehearsal on the CPU passes False
        try:
            device.require_chips(cell.chips)
        except device.NoAccelerator as e:
            print(f"attributed_run: {e}", file=sys.stderr)
            return 1
    cache = device.setup_compile_cache()
    kind = cell.traffic["driver"]
    tracer = DeviceTracer(root, f"{cell.name}.attributed")
    work = tracer.dir.parent / f"{cell.name}.spans"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    spans_path = work / "spans.trace.json"
    if args.spans:
        tracing = {"enabled": True, "max_events": MAX_EVENTS,
                   "trace_path": str(spans_path)}
        if kind == "train_steps":
            cell.config["ds_config"] = dict(cell.config["ds_config"],
                                            telemetry={"tracing": tracing})
        else:
            cell.config["server_config"] = dict(
                cell.config.get("server_config", {}), tracing=tracing)
    log(f"[attributed] {cell.name}: seed {args.seed}, {args.seconds:g} s, "
        f"spans {args.spans}, capture {args.capture}; compile cache {cache}")
    cap = Capturer(device.CompileCounter(), tracer, args.seconds,
                   min(CAPTURE_S[kind], args.seconds / 3),
                   bool(args.capture))
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
    run.spans = events
    run.counters.setdefault(
        "window_mono_us", (cap.mark_mono * 1e6,
                           (cap.mark_mono + args.seconds) * 1e6))
    if cap.capture_mono:
        run.counters["capture_mono_us"] = tuple(t * 1e6
                                                for t in cap.capture_mono)
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "spans": args.spans,
           "capture": args.capture, "device": device.facts(),
           "end_to_end": dict(run.end_to_end, setup_s=run.setup_s),
           "span_events": len(events)}
    pb = trace.find_xplane(str(tracer.dir)) if cap.capture_mono else None
    capture = attribute.load_capture(pb) if pb else None
    if capture is not None and capture.planes:  # none in a CPU rehearsal
        att = attribute.attribute_capture(capture, events)
        run.attribution = att
        run.trace = trace.reduce_planes(capture.planes)
        err = att.clock_error_s
        log(f"[attributed] clock error "
            f"{'no bracket found' if err is None else f'{err * 1e3:.4f} ms'}"
            f"; Tracer against host plane drifted {att.drift_s * 1e6:.1f} us "
            f"between the anchors; {len(capture.anchors)} anchors, "
            f"{len(capture.enqueues)} enqueues, {len(capture.dones)} dones")
        out["clock_error_ms"] = None if err is None else err * 1e3
        out["device"].update(busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = {
            "device_ops": run.trace.top_ops, "idle_gaps": att.idle_gaps,
            "idle_s": att.idle_s, "long_idle_s": att.long_idle_s,
            "named_s": att.named_s,
            "pallas": {k: v for k, v in att.pallas.items()}}
        if args.keep:
            keep = root / args.keep
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(pb, keep / f"{cell.name}.xplane.pb")
            if spans_path.exists():
                shutil.copy(spans_path, keep / f"{cell.name}.spans.json")
    readers = load_code(root, "readers", "spans")
    host = load_code(root, "readers", "host")
    dev = load_code(root, "readers", "device")
    metrics = {}
    for name, fn in (
            ("train_step_span_ms_p50", readers.train_step_span_ms_p50),
            ("step_ms_p50.train", host.step_ms_p50),
            ("serve_host_ms_p50", readers.serve_host_ms_p50),
            ("serve_step_ms_p50", host.serve_step_ms_p50),
            ("idle_attributed_share", readers.idle_attributed_share),
            ("paged_roofline", readers.paged_roofline),
            ("device_idle_share", dev.device_idle_share),
            ("pallas_time_share", dev.pallas_time_share)):
        value = fn(run, cell)
        if value is not None:
            metrics[name] = float(value)
    out["metrics"] = metrics
    shutil.rmtree(work, ignore_errors=True)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
