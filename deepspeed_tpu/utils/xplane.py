"""XPlane trace analysis: device-op timelines and collective/compute
overlap.

The on-chip counterpart of the Domino overlap claim (ref
blogs/deepspeed-domino/README.md:126 — "50-100% of the communication is
hidden"): given an XPlane capture (``jax.profiler.start_trace``), extract
each TPU device plane's op events, classify them as collectives
(all-reduce / all-gather / reduce-scatter / collective-permute /
all-to-all) or compute (fusion / dot / convolution / custom-call), and
measure what fraction of collective wall-time overlaps compute on the
same device — the direct evidence that XLA scheduled chunk B's matmuls
under chunk A's all-reduce.

Parsing uses the xplane proto bundled with tensorflow
(``tensorflow.tsl.profiler.protobuf.xplane_pb2``); everything here is
pure-host analysis, importable without a TPU.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

_COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")
# NOTE: no "while" here — the scan-loop parent event spans the whole
# layer loop (collectives included) and would count every in-loop
# collective as hidden, inflating the metric toward 1.0
_COMPUTE_MARKERS = ("fusion", "dot", "convolution", "custom-call")
# loops and branches are events too and span their bodies' operations
_CONTROL_OPS = ("while", "conditional", "call")
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_NUMBER = re.compile(r"\.\d+$")
# a transformation wrapped around a scope of a name stack:
# ``jit(step)/transpose(jvp(mlp))/while/body/checkpoint/attn.qkv/dot_general:``
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_UNSCOPED = "unscoped"


def find_xplane_files(logdir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))


def load_xspace(path: str):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def device_op_intervals(xspace, device_substr: str = "TPU"
                        ) -> Dict[str, Dict[str, List[Tuple[int, int]]]]:
    """Per device plane: {"collective": [(start_ps, end_ps)...],
    "compute": [...]} from the XLA-op lines."""
    out: Dict[str, Dict[str, List[Tuple[int, int]]]] = {}
    for plane in xspace.planes:
        if device_substr not in plane.name:
            continue
        buckets = {"collective": [], "compute": []}
        meta = plane.event_metadata
        # TPU device planes carry several hierarchy lines ("XLA Modules",
        # "Steps", "XLA Ops"); only the op-level line has leaf events —
        # parent module/step spans would swallow the collectives.
        op_lines = [ln for ln in plane.lines if "op" in ln.name.lower()]
        for line in (op_lines or plane.lines):
            base = line.timestamp_ns * 1000  # → ps
            for ev in line.events:
                name = meta[ev.metadata_id].name.lower()
                start = base + ev.offset_ps
                end = start + ev.duration_ps
                if any(m in name for m in _COLLECTIVE_MARKERS):
                    buckets["collective"].append((start, end))
                elif any(m in name for m in _COMPUTE_MARKERS):
                    buckets["compute"].append((start, end))
        if buckets["collective"] or buckets["compute"]:
            out[plane.name] = buckets
    return out


def _merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def overlap_fraction(collective: Sequence[Tuple[int, int]],
                     compute: Sequence[Tuple[int, int]]) -> float:
    """Fraction of total collective time that coincides with compute on
    the same timeline.  1.0 = fully hidden communication."""
    coll = _merge(collective)
    comp = _merge(compute)
    total = sum(e - s for s, e in coll)
    if total == 0:
        return 0.0
    covered = 0
    j = 0
    for s, e in coll:
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            covered += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
    return covered / total


def _is_control_op(name: str) -> bool:
    """A loop, branch or call: its event spans the operations of its
    body, which are events of their own.  ``name``: an instruction's
    whole text (a TPU capture) or its bare name."""
    _, eq, rhs = name.partition(" = ")
    m = _OPCODE.search(rhs) if eq else None
    stem = m.group(1) if m else _NUMBER.sub("", name.lstrip("%").strip())
    return stem in _CONTROL_OPS


def _stage_of(name_stack: str, first: bool = False) -> str:
    """The stage (``telemetry.tracing.STAGE_NAMES``) an operation belongs
    to, from its JAX name stack (the ``tf_op`` stat of its event
    metadata): the LAST scope of the stack that is a stage name (the
    innermost: an operation of the prediction module's attention reads
    ``attn.read``), or with ``first`` the outermost (``mtp``);
    ``unscoped`` where none is.  Transformations wrapped around a
    scope (``transpose(jvp(mlp))``) are read through; a jitted function's
    own name (``jit(loss)``) is not a scope."""
    from deepspeed_tpu.telemetry.tracing import STAGE_NAMES

    found = []
    # the last segment is the primitive (or, alone, an argument's name:
    # ``params['embed']:`` is no scope)
    for seg in name_stack.split("/")[:-1]:
        while (m := _WRAPPED.match(seg)) and m.group(1) not in ("jit",
                                                                "pjit"):
            seg = m.group(2)
        if seg in STAGE_NAMES:
            found.append(seg)
    if not found:
        return _UNSCOPED
    return found[0] if first else found[-1]


def _metadata_stat(plane, md, stat_name: str) -> str:
    """One stat of an event's METADATA (where XLA's per-instruction
    facts live: ``tf_op``, ``hlo_category``, ``program_id``), as text."""
    for st in md.stats:
        if plane.stat_metadata[st.metadata_id].name != stat_name:
            continue
        kind = st.WhichOneof("value")
        value = getattr(st, kind)
        if kind == "ref_value":
            return plane.stat_metadata[value].name
        return value.decode(errors="replace") if isinstance(value, bytes) \
            else str(value)
    return ""


def _leaf_ops(xspace, device_substr: str, async_spans: bool = False):
    """``(plane, metadata, duration_ps)`` of every operation of the
    op-level lines that is not a loop or a branch, over the planes that
    match (all planes where none does: a CPU capture)."""
    matched = [p for p in xspace.planes if device_substr in p.name]
    for plane in (matched or xspace.planes):
        meta = plane.event_metadata
        # "XLA Ops" is the instruction stream; "Async XLA Ops" spans each
        # asynchronous copy or collective from its start to its done,
        # over other operations, under the start's name
        op_lines = [ln for ln in plane.lines if "op" in ln.name.lower()
                    and (async_spans or "async" not in ln.name.lower())]
        control: Dict[int, bool] = {}     # one answer an instruction
        for line in (op_lines or plane.lines):
            for ev in line.events:
                md = meta[ev.metadata_id]
                skip = control.get(ev.metadata_id)
                if skip is None:
                    skip = control[ev.metadata_id] = _is_control_op(md.name)
                if not skip:
                    yield plane, md, ev.duration_ps


def _op_totals(xspace, device_substr: str,
               async_spans: bool = False) -> List[Dict]:
    """Every leaf op-line event added up by metadata name, largest first:
    ``[{"name", "total_ms", "count", "stage"}, ...]``.  ``async_spans``
    adds, to an asynchronous operation's start, its time from start to
    done (the "Async XLA Ops" line: time on the wire, hidden or not, which
    is no self time and not busy time)."""
    totals: Dict[str, List] = {}
    for plane, md, ps in _leaf_ops(xspace, device_substr, async_spans):
        rec = totals.get(md.name)
        if rec is None:
            rec = totals[md.name] = [
                0.0, 0, _stage_of(_metadata_stat(plane, md, "tf_op"))]
        rec[0] += ps / 1e9  # ps → ms
        rec[1] += 1
    ranked = sorted(totals.items(), key=lambda kv: -kv[1][0])
    return [{"name": n, "total_ms": round(t, 4), "count": c, "stage": st}
            for n, (t, c, st) in ranked]


def top_device_ops(xspace, device_substr: str = "TPU",
                   k: int = 10) -> List[Dict]:
    """Top-k device ops by total self time across matching planes.

    Aggregates leaf op-line events by metadata name (loops, branches and
    calls, whose events span their bodies, are left out, and the "Async
    XLA Ops" line, whose events span other operations: an asynchronous
    collective is its start's and its done's self times); returns
    ``[{"name", "total_ms", "count", "stage"}, ...]`` sorted by total
    time, ``stage`` by :func:`_stage_of`.  When no plane matches
    ``device_substr`` (e.g. a CPU capture, host events only), falls back
    to every plane that has op-shaped lines so the caller still sees
    *something* — flagged by the caller, not here."""
    return _op_totals(xspace, device_substr)[:k]


def _collective_ops(xspace, device_substr: str = "TPU") -> List[Dict]:
    """Every collective of the capture, however small beside the ten
    largest operations, in ``top_device_ops``' shape: an asynchronous one
    by its time from start to done plus its self times, under the start's
    name.  What :func:`dominant_collective` ranks for a capture report."""
    return [op for op in _op_totals(xspace, device_substr, async_spans=True)
            if classify_op(op["name"]) == "collective"]


def time_by_stage(xspace, device_substr: str = "TPU") -> Dict:
    """Device time by stage of the model: every leaf operation's time
    goes to the stage its name stack says (:func:`_stage_of`), loops and
    branches left out.  ``{"total_ms", "stages": {stage: ms} by the
    innermost stage, "outer": {stage: ms} by the outermost (``mtp`` as a
    whole), "unscoped_share"}``, summed over the matching planes.  A
    fusion is one operation and carries its root's name stack: what
    another stage's operations cost inside it goes to the root's."""
    inner: Dict[str, float] = {}
    outer: Dict[str, float] = {}
    stages: Dict[Tuple[int, int], Tuple[str, str]] = {}
    for plane, md, ps in _leaf_ops(xspace, device_substr):
        key = (id(plane), md.id)
        if key not in stages:
            stack = _metadata_stat(plane, md, "tf_op")
            stages[key] = (_stage_of(stack), _stage_of(stack, first=True))
        last, first = stages[key]
        inner[last] = inner.get(last, 0.0) + ps / 1e9
        outer[first] = outer.get(first, 0.0) + ps / 1e9
    total = sum(inner.values())

    def table(d):
        return {k: round(v, 4) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    return {"total_ms": round(total, 4), "stages": table(inner),
            "outer": table(outer),
            "unscoped_share": round(inner.get(_UNSCOPED, 0.0) / total, 4)
            if total else 0.0}


def classify_op(name: str) -> str:
    """``"collective"`` / ``"compute"`` / ``"other"`` for one device-op
    name — the same marker tables the overlap fraction uses, exposed so
    report consumers (the overlap scheduler) classify identically."""
    n = name.lower()
    if any(m in n for m in _COLLECTIVE_MARKERS):
        return "collective"
    if any(m in n for m in _COMPUTE_MARKERS):
        return "compute"
    return "other"


def dominant_collective(top_ops: Sequence[Dict]) -> Optional[Dict]:
    """Largest collective by total self time in a ``top_device_ops``-shaped
    table → ``{"name", "total_ms"}`` (``None`` when no op classifies as a
    collective — e.g. a CPU capture's host planes)."""
    best: Optional[Dict] = None
    for op in top_ops or ():
        if classify_op(op.get("name", "")) != "collective":
            continue
        if best is None or op.get("total_ms", 0.0) > best["total_ms"]:
            best = {"name": op["name"],
                    "total_ms": float(op.get("total_ms", 0.0))}
    return best


def analyze_logdir(logdir: str, device_substr: str = "TPU") -> Dict:
    """Aggregate overlap stats over every device plane in a capture."""
    files = find_xplane_files(logdir)
    if not files:
        return {"error": f"no xplane files under {logdir}"}
    per_device = {}
    for path in files:
        for dev, b in device_op_intervals(load_xspace(path),
                                          device_substr).items():
            # multi-host captures: every host names its plane
            # /device:TPU:0 — key by file too so hosts don't overwrite
            if len(files) > 1:
                dev = f"{os.path.basename(path)}:{dev}"
            frac = overlap_fraction(b["collective"], b["compute"])
            per_device[dev] = {
                "overlap_fraction": round(frac, 4),
                "collective_ms": round(sum(e - s for s, e
                                           in _merge(b["collective"]))
                                       / 1e9, 3),
                "compute_ms": round(sum(e - s for s, e
                                        in _merge(b["compute"])) / 1e9, 3),
            }
    if not per_device:
        return {"error": "no device planes matched "
                         f"{device_substr!r} (CPU captures carry host "
                         "events only)"}
    fracs = [d["overlap_fraction"] for d in per_device.values()]
    return {"devices": per_device,
            "mean_overlap_fraction": round(sum(fracs) / len(fracs), 4)}
