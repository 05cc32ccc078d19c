"""Put a traced stretch's idle gaps down to the program span the host was
in, and its Pallas seconds to kernels by their own names.

Three clocks meet in one capture (read by hand from
``tests/benchmark/data/span_trace.xplane.pb`` and its elder):

- the program's ``Tracer`` spans run on ``time.monotonic()``;
- the capture's ``/host:CPU`` plane runs on the profiler's host clock.
  ``deepspeed_tpu/utils/trace.py:write_clock_anchor`` writes an annotation
  named ``dstpu.clock_anchor`` whose ``monotonic_ns`` argument is the
  monotonic clock at the annotation's start: one anchor gives the offset,
  two (capture start and stop) the drift between them;
- each ``/device:TPU:<n>`` plane runs on that device's clock, about a
  millisecond EARLY against the host plane as ``ProfileData`` reads them.
  The runtime's own host events bracket the offset: a program cannot start
  on the device before ``DoEnqueueProgram`` (same ``run_id`` as its
  ``XLA Modules`` event) began, and ``tpu::System::Execute=>Done`` cannot
  begin before the program ended.  Half the bracket's width is the clock
  error; a gap shorter than it is not attributed.

Everything here takes plain tuples, so the arithmetic is tested on planes
made by hand; ``load_capture`` alone reads a file.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.lib import trace

Interval = Tuple[float, float]

ANCHOR = "dstpu.clock_anchor"           # utils/trace.py CLOCK_ANCHOR
ENQUEUE, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
HOST_PLANE = "/host:CPU"
_NUMBER = re.compile(r"\.\d+$")
SHORT = "unattributed: shorter than the clock error"


@dataclass
class Capture:
    """What the attribution needs of one ``.xplane.pb``; times in ns on
    the plane's own clock."""
    planes: List[trace.DevicePlane]
    # per device plane, by ordinal: (run_id, start, end) of each program
    runs: Dict[int, List[Tuple[int, float, float]]] = field(
        default_factory=dict)
    anchors: List[Tuple[float, int]] = field(default_factory=list)
    enqueues: List[Tuple[float, int, int]] = field(default_factory=list)
    dones: List[Tuple[float, int]] = field(default_factory=list)


def load_capture(path: str) -> Capture:
    from jax.profiler import ProfileData

    cap = Capture(planes=[])
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            dp = trace.DevicePlane(plane.name)
            ordinal = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    dp.ops = [(ev.name, ev.start_ns, ev.duration_ns)
                              for ev in line.events]
                elif line.name == trace.MODULES_LINE:
                    evs = list(line.events)
                    dp.modules = [(ev.name, ev.start_ns, ev.duration_ns)
                                  for ev in evs]
                    cap.runs[ordinal] = [
                        (int(dict(ev.stats).get("run_id", -1)), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in evs]
            cap.planes.append(dp)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        cap.anchors.append(
                            (ev.start_ns,
                             int(dict(ev.stats)["monotonic_ns"])))
                    elif ev.name == ENQUEUE:
                        st = dict(ev.stats)
                        cap.enqueues.append(
                            (ev.start_ns, int(st.get("run_id", -1)),
                             int(st.get("device_ordinal", 0))))
                    elif ev.name == DONE:
                        cap.dones.append(
                            (ev.start_ns,
                             int(dict(ev.stats).get("core_id", 0))))
    cap.anchors.sort()
    cap.enqueues.sort()
    cap.dones.sort()
    return cap


# -- Tracer clock <-> host plane -------------------------------------------
@dataclass
class AnchorMap:
    """``host_ns = scale * (monotonic_ns - m0) + h0``.  ``drift_ns``: how
    far the two clocks moved apart between the first and last anchor
    (0 with one anchor, where ``scale`` is 1)."""
    m0: float
    h0: float
    scale: float
    drift_ns: float

    def to_host(self, monotonic_ns: float) -> float:
        return self.scale * (monotonic_ns - self.m0) + self.h0

    def to_monotonic(self, host_ns: float) -> float:
        return (host_ns - self.h0) / self.scale + self.m0


def anchor_map(anchors: Sequence[Tuple[float, int]]) -> Optional[AnchorMap]:
    """From ``(host_ns, monotonic_ns)`` anchors; None without one."""
    if not anchors:
        return None
    (h0, m0), (h1, m1) = anchors[0], anchors[-1]
    if m1 == m0:
        return AnchorMap(m0, h0, 1.0, 0.0)
    return AnchorMap(m0, h0, (h1 - h0) / (m1 - m0), (h1 - h0) - (m1 - m0))


# -- host plane <-> device plane ---------------------------------------------
def device_offset(runs: Sequence[Tuple[int, float, float]],
                  enqueues: Sequence[Tuple[float, int]],
                  dones: Sequence[float]
                  ) -> Optional[Tuple[float, float]]:
    """``(lo, hi)`` with ``host_ns = device_ns + d`` for some d in it.

    ``lo``: no run starts before its enqueue began (matched by run id),
    so d >= enqueue - start for every pair.  ``hi``: the k-th ``Done``
    begins after the k-th run ended, d <= done - end.  A capture that
    starts or stops mid-flight holds a ``Done`` without its run or a run
    without its ``Done``, so the two lists may be shifted against each
    other; of the shifts tried, those that pair a ``Done`` with a LATER
    run give a ``hi`` under ``lo`` and are thrown out, those that pair it
    with an earlier run give a looser ``hi``: the tightest one left is
    taken.  None where either side finds no pair."""
    start = {rid: s for rid, s, _ in runs}
    los = [t - start[rid] for t, rid in enqueues if rid in start]
    if not los or not dones:
        return None
    lo = max(los)
    ends = sorted(e for _, _, e in runs)
    dones = sorted(dones)
    best = None
    for shift in range(-3, 4):      # done k against run k + shift
        his = [dones[k] - ends[k + shift] for k in range(len(dones))
               if 0 <= k + shift < len(ends)]
        if his and min(his) >= lo and (best is None or min(his) < best):
            best = min(his)
    return None if best is None else (lo, best)


# -- gaps and who was in them ------------------------------------------------
def idle_gaps_of(plane: trace.DevicePlane) -> List[Interval]:
    """Every stretch of the plane's own clock in which no operation ran,
    between its first and last operation."""
    merged = trace.merge([(s, s + d) for _, s, d in plane.leaf()])
    return [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]


def loop_spans(events: Sequence[dict]) -> List[dict]:
    """The spans that say what the host was doing: those sharing a trace
    id with a ``*.dispatch`` span (the serve loop's and the ragged
    step's, or the train step's).  A request's lifetime spans
    (``serve.request``, ``serve.decode``, ...) carry the request's own
    id and cover whatever happens meanwhile.  All spans where none is a
    dispatch."""
    spans = [e for e in events if e.get("ph") == "X"]
    ids = {e["args"].get("trace_id") for e in spans
           if e["name"].endswith(".dispatch")}
    return [e for e in spans if e["args"].get("trace_id") in ids] \
        if ids else spans


def attribute(gaps: Sequence[Interval],
              spans: Sequence[Tuple[float, float, str]], min_len: float,
              unnamed: Optional[Sequence[str]] = None) -> Dict[str, float]:
    """Length of the gaps by the name of the innermost span over each
    piece of them; ``spans`` are ``(start, end, name)`` on the gaps'
    clock.  The innermost of the spans over a piece is the one that
    began last (spans of one loop nest).  A piece under no span goes to
    its gap's entry of ``unnamed`` (the name the gap had before there
    were spans; ``""`` without the list); a gap shorter than ``min_len``
    goes whole to ``SHORT``."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    out: Dict[str, float] = {}
    for i, (g0, g1) in enumerate(gaps):
        if g1 - g0 < min_len:
            out[SHORT] = out.get(SHORT, 0.0) + (g1 - g0)
            continue
        over = [sp for sp in spans[:bisect.bisect_left(starts, g1)]
                if sp[1] > g0]
        cuts = sorted({g0, g1, *[t for s, e, _ in over for t in (s, e)
                                 if g0 < t < g1]})
        for a, b in zip(cuts, cuts[1:]):
            inner = [sp for sp in over if sp[0] <= a and sp[1] >= b]
            name = (max(inner, key=lambda sp: (sp[0], -sp[1]))[2] if inner
                    else unnamed[i] if unnamed else "")
            out[name] = out.get(name, 0.0) + (b - a)
    return out


# -- Pallas kernels by name ---------------------------------------------------
def kernel_name(text: str) -> str:
    """``flash_fwd`` of ``%flash_fwd.3 = ... custom-call(...)``: the
    instruction's name without the number XLA appends, and without the
    transformations JAX wraps around a scope's name where the kernel is
    differentiated outside any ``jax.checkpoint``
    (``transpose(jvp(flash_bwd_dq))`` reaches the profiler as
    ``transpose_jvp_flash_bwd_dq__``: one trailing ``_`` a wrapper)."""
    name = _NUMBER.sub("", text.partition(" = ")[0].lstrip("%").strip())
    wrappers = len(name) - len(name.rstrip("_"))
    parts = name.rstrip("_").split("_", wrappers)
    # fewer parts than wrappers: no name of its own, keep what is shown
    return parts[-1] if len(parts) > wrappers else name


def pallas_ns(plane: trace.DevicePlane) -> Dict[str, Tuple[float, int]]:
    """``{kernel name: (ns, calls)}`` of the plane's Pallas kernels."""
    out: Dict[str, Tuple[float, int]] = {}
    for text, _, d in plane.leaf():
        if trace.is_mosaic(text):
            ns, n = out.get(kernel_name(text), (0.0, 0))
            out[kernel_name(text)] = (ns + d, n + 1)
    return out


# -- all of it for one traced stretch ----------------------------------------
@dataclass
class Attribution:
    """Seconds are per chip (means over the chips that ran anything)."""
    clock_error_s: Optional[float]      # None: no bracket was found
    drift_s: float                      # Tracer against host plane
    stretch_mono_us: Optional[Tuple[float, float]]  # anchors, Tracer clock
    idle_s: float                       # all gaps
    long_idle_s: float                  # gaps longer than the clock error
    named_s: float                      # ...under a named span
    idle_gaps: List[List]               # [[span name or old name, s], ...]
    pallas: Dict[str, List[float]]      # name -> [seconds, calls]
    # Pallas events on the Tracer's clock, first chip: (name, t0_us, t1_us)
    pallas_events: List[Tuple[str, float, float]]


def attribute_capture(cap: Capture, events: Sequence[dict],
                      top: int = 12) -> Attribution:
    """``events``: the program's Tracer events (Chrome trace events, ts
    and dur in us on the monotonic clock)."""
    planes = [p for p in cap.planes if p.ops]
    if not planes:
        raise ValueError("the capture holds no device operation")
    amap = anchor_map(cap.anchors)
    spans_mono = [(e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3, e["name"])
                  for e in loop_spans(events)]
    n = len(planes)
    idle = long_idle = named = 0.0
    worst = 0.0
    bracketed = amap is not None
    by_name: Dict[str, float] = {}
    pallas: Dict[str, List[float]] = {}
    pallas_events: List[Tuple[str, float, float]] = []
    for p in planes:
        ordinal = int(p.name.rsplit(":", 1)[1])
        gaps = idle_gaps_of(p)
        idle += sum(b - a for a, b in gaps)
        for name, (ns, calls) in pallas_ns(p).items():
            got = pallas.setdefault(name, [0.0, 0.0])
            got[0] += ns * 1e-9 / n
            got[1] += calls / n
        bracket = device_offset(
            cap.runs.get(ordinal, []),
            [(t, rid) for t, rid, dev in cap.enqueues if dev == ordinal],
            [t for t, core in cap.dones if core == ordinal]) \
            if amap is not None else None
        mods = sorted(p.modules, key=lambda m: m[1])
        if bracket is None:
            # no common clock: every gap keeps the name it had
            bracketed = False
            for a, b in gaps:
                what = trace._between(mods, a, b)
                by_name[what] = by_name.get(what, 0.0) + (b - a)
            continue
        lo, hi = bracket
        d, err = (lo + hi) / 2, (hi - lo) / 2
        worst = max(worst, err)

        def mono(t, d=d):       # device ns -> Tracer ns
            return amap.to_monotonic(t + d)
        got = attribute([(mono(a), mono(b)) for a, b in gaps], spans_mono,
                        err, [trace._between(mods, a, b) for a, b in gaps])
        long_idle += sum(b - a for a, b in gaps if b - a >= err)
        for name, ns in got.items():
            by_name[name] = by_name.get(name, 0.0) + ns
            if not name.startswith("unattributed"):
                named += ns
        if not pallas_events:
            pallas_events = [
                (kernel_name(text), mono(s) / 1e3, mono(s + dur) / 1e3)
                for text, s, dur in p.leaf() if trace.is_mosaic(text)]
    stretch = None
    if amap is not None:
        stretch = (cap.anchors[0][1] / 1e3, cap.anchors[-1][1] / 1e3)
    ns = 1e-9
    return Attribution(
        clock_error_s=worst * ns if bracketed else None,
        drift_s=(amap.drift_ns * ns if amap else 0.0),
        stretch_mono_us=stretch, idle_s=idle / n * ns,
        long_idle_s=long_idle / n * ns, named_s=named / n * ns,
        idle_gaps=[[k, v / n * ns] for k, v in
                   sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        pallas={k: v for k, v in sorted(pallas.items(),
                                        key=lambda kv: -kv[1][0])},
        pallas_events=pallas_events)
