"""From a profiler trace to device busy time, kernel time and gaps.

The logic of ``deepspeed_tpu/utils/xplane.py`` (merge intervals, collective
time not covered by compute, top operations) on ``jax.profiler.ProfileData``,
which reads an ``.xplane.pb`` with nothing but JAX.  What a trace of this
chip looks like (read by hand from ``tests/benchmark/data/
small_trace.xplane.pb``, recorded on a v5e by
``benchmark/tools/record_small_trace.py``):

- one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` is the
  TensorCore's instruction stream, one event per executed HLO instruction,
  named by the instruction's whole text
  (``%fusion.3 = bf16[..] fusion(..), kind=kLoop, ...``); ``XLA Modules``
  has one event per program run (``jit_train_step(<fingerprint>)``);
  ``Async XLA Ops`` spans each asynchronous copy or collective from its
  start to its done;
- a Pallas kernel is a ``custom-call`` whose text carries
  ``custom_call_target="tpu_custom_call"`` (other custom calls, such as
  ``ConcatBitcast``, take no time);
- loops (``while``) and branches are events too and span their bodies, so
  they are left out wherever time is added up;
- times are nanoseconds on the device's own clock, not the host's.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
_CONTROL = ("while", "conditional", "call")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


def opcode(text: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event name, "" if it has none."""
    _, _, rhs = text.partition(" = ")
    m = _OPCODE.search(rhs)
    return m.group(1) if m else ""


def short_name(text: str) -> str:
    """``<instruction name> <opcode>`` (and ``pallas`` for a Pallas
    kernel), without shapes: short enough for a breakdown."""
    name = text.partition(" = ")[0].lstrip("%").strip()
    tag = "pallas" if MOSAIC_TARGET in text else opcode(text)
    return f"{name} {tag}".strip()[:96]


def is_collective(text: str) -> bool:
    return opcode(text).startswith(_COLLECTIVES)


def is_control(text: str) -> bool:
    return opcode(text) in _CONTROL


def is_mosaic(text: str) -> bool:
    return MOSAIC_TARGET in text


@dataclass
class DevicePlane:
    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)

    def leaf(self) -> List[Tuple[str, float, float]]:
        return [op for op in self.ops if not is_control(op[0])]


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load_device_planes(path: str) -> List[DevicePlane]:
    """The device planes of an ``.xplane.pb``: ``(name, start_ns,
    duration_ns)`` of every op and every program run."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        dp = DevicePlane(plane.name)
        for line in plane.lines:
            if line.name == OPS_LINE:
                dp.ops = [(ev.name, ev.start_ns, ev.duration_ns)
                          for ev in line.events]
            elif line.name == MODULES_LINE:
                dp.modules = [(ev.name, ev.start_ns, ev.duration_ns)
                              for ev in line.events]
        planes.append(dp)
    return planes


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """The length of ``a`` (merged) that ``b`` (merged) does not cover."""
    a, b = merge(a), merge(b)
    total = sum(e - s for s, e in a)
    covered = 0.0
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total - covered


def _iv(ops) -> List[Interval]:
    return [(s, s + d) for _, s, d in ops]


@dataclass
class Reduction:
    """What the metrics read.  Seconds; per-chip values are means over the
    chips that ran anything."""
    chips: int
    window_s: float             # first op's start to last op's end
    busy_s: float               # union of the leaf ops
    mosaic_s: float             # Pallas kernels
    mosaic_calls: float
    collective_s: float         # union of the collective ops
    exposed_collective_s: float  # ...while no other op ran
    top_ops: List[List]         # [[short name, seconds a chip], ...]
    idle_gaps: List[List]       # [[what ran before and after, seconds], ...]


def reduce_planes(planes: Sequence[DevicePlane], top: int = 10) -> Reduction:
    planes = [p for p in planes if p.ops]
    if not planes:
        raise ValueError("the trace holds no device operation")
    n = len(planes)
    t0 = min(s for p in planes for _, s, _ in p.ops)
    t1 = max(s + d for p in planes for _, s, d in p.ops)
    busy = mosaic = calls = coll = exposed = 0.0
    totals: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    for p in planes:
        leaf = p.leaf()
        colls = [op for op in leaf if is_collective(op[0])]
        rest = [op for op in leaf if not is_collective(op[0])]
        busy += measure(_iv(leaf))
        coll += measure(_iv(colls))
        exposed += subtract(_iv(colls), _iv(rest))
        for name, _, d in leaf:
            if is_mosaic(name):
                mosaic += d
                calls += 1
            key = short_name(name)
            totals[key] = totals.get(key, 0.0) + d
        mods = sorted(p.modules, key=lambda m: m[1])
        merged = merge(_iv(leaf))
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, _between(mods, e0, s1)))
    ns = 1e-9
    gap_tot: Dict[str, float] = {}
    for d, what in gaps:
        gap_tot[what] = gap_tot.get(what, 0.0) + d
    return Reduction(
        chips=n, window_s=(t1 - t0) * ns, busy_s=busy / n * ns,
        mosaic_s=mosaic / n * ns, mosaic_calls=calls / n,
        collective_s=coll / n * ns, exposed_collective_s=exposed / n * ns,
        top_ops=[[k, v / n * ns] for k, v in
                 sorted(totals.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v / n * ns] for k, v in
                   sorted(gap_tot.items(), key=lambda kv: -kv[1])[:top]])


def _module_at(mods, t: float) -> str:
    for name, s, d in mods:
        if s <= t <= s + d:
            return name.partition("(")[0]
    return ""


def _between(mods, e0: float, s1: float) -> str:
    """Name an idle gap by the programs on either side of it.  The
    program's host spans are not on this clock yet, so what the HOST did
    in the gap is unattributed."""
    a, b = _module_at(mods, e0), _module_at(mods, s1)
    if a and a == b and _module_at(mods, (e0 + s1) / 2):
        return f"unattributed: inside {a}"
    return f"unattributed: after {a or '?'} before {b or '?'}"
