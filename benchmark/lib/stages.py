"""Device time by stage of the model, by compiled program and by step kind.

The program wraps each stage of a jitted step in ``jax.named_scope`` with a
name of ``deepspeed_tpu.telemetry.tracing.STAGE_NAMES``; the scope joins the
JAX name stack of every operation traced inside it.  What a capture of this
chip holds of that (read by hand from ``tests/benchmark/data/
span_trace.xplane.pb``, which has no scope yet, and from
``stage_trace.xplane.pb``, recorded on a v5e by
``benchmark/tools/record_stage_trace.py``):

- every event of a device plane's ``XLA Ops`` line points at an event
  METADATA entry, one an instruction of one compiled program, and the
  instruction's facts are stats of that entry, not of the event: ``tf_op``
  (the name stack and the primitive,
  ``jit(ragged_step_sampled)/while/body/attn.qkv/dot_general:``; for a
  fusion, its root's; an argument's copy carries the argument's name,
  ``q:``; an operation the compiler made itself has none), ``program_id``
  (the compiled program: one a bucket pair of the ragged step),
  ``hlo_category``, ``flops``, ``bytes_accessed``.
  ``jax.profiler.ProfileData``'s ``event.stats`` shows the three per-event
  stats only (``device_offset_ps``, ``device_duration_ps``, a time scale),
  so the file is read with the proto
  (``tensorflow.tsl.profiler.protobuf.xplane_pb2``); an event's start is
  ``line.timestamp_ns + offset_ps / 1000``, which is ``ProfileData``'s
  ``start_ns`` (a test holds the two equal), so these times sit on the
  clocks ``lib.attribute`` maps;
- an ``XLA Modules`` event is one run of a program, named
  ``jit_<function>(<program id>)``, with a ``run_id`` stat of its own;
- under ``jax.grad``, ``jax.checkpoint`` and ``lax.scan`` the stack reads
  ``transpose(jvp(mlp))``, ``checkpoint/rematted_computation/mlp``,
  ``while/body/closed_call/mlp``: a scope is a whole segment between two
  ``/``, looked for inside the transformations wrapped around it; a jitted
  function's name (``jit(loss)``) is not a scope, nor is the last segment
  (the primitive; alone, an argument's name: ``params['embed']['tokens']:``
  is the copy of a weight, not the embedding);
- the plane ``/host:metadata`` holds every traced program's ``HloProto``
  (stat ``Hlo Proto`` of the event metadata whose id is the program id):
  the fused computations with each instruction's own ``op_name``, which is
  how the fusions that mix stages are counted
  (``tensorflow.compiler.xla.service.hlo_pb2``).

Everything but ``load_stage_capture`` and ``mixed_fusions_of`` takes plain
tuples, so the arithmetic is tested on planes made by hand.
"""

from __future__ import annotations

import bisect
import functools
import re
import statistics
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from benchmark.lib import trace

UNSCOPED = "unscoped"
# the scope around the layer loop: what the loop does itself (a layer's
# weights sliced out of their stacks).  Such a slice fused into the product
# that reads it belongs to that product's stage, so a fusion of the loop's
# name and ONE other stage mixes nothing
LOOP = "layers"
# (name stack, program id, start ns, duration ns, instruction text)
Op = Tuple[str, int, float, float, str]
# (program id, run id, start ns, end ns, program name)
RunOf = Tuple[int, int, float, float, str]

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")
_NOT_A_SCOPE = ("jit", "pjit")


def stage_names() -> Tuple[str, ...]:
    """The program's vocabulary; empty for a program without one (the
    parent of the PR that added it), where every operation is unscoped."""
    try:
        from deepspeed_tpu.telemetry.tracing import STAGE_NAMES
    except ImportError:
        return ()
    return tuple(STAGE_NAMES)


def stage_of(stack: str, names: Iterable[str], first: bool = False) -> str:
    """The stage a name stack says: the LAST scope on it that is a stage
    name (the innermost), or with ``first`` the outermost; ``UNSCOPED``
    where none is."""
    return _stage_of(stack, names if isinstance(names, frozenset)
                     else frozenset(names), first)


@functools.lru_cache(maxsize=None)      # a capture has a few thousand stacks
def _stage_of(stack: str, names: frozenset, first: bool) -> str:
    found = []
    # the last segment is the primitive, or an argument's whole name
    for seg in stack.split("/")[:-1]:
        while (m := _WRAPPED.match(seg)) and m.group(1) not in _NOT_A_SCOPE:
            seg = m.group(2)
        if seg in names:
            found.append(seg)
    if not found:
        return UNSCOPED
    return found[0] if first else found[-1]


def leaf(ops: Iterable[Op]) -> List[Op]:
    """Without loops, branches and calls, whose events span their bodies."""
    return [op for op in ops if not trace.is_control(op[4])]


def by_stage(ops: Iterable[Op], names: Iterable[str], first: bool = False
             ) -> Dict[str, float]:
    """ns of the leaf operations by stage, largest first."""
    names = frozenset(names)
    out: Dict[str, float] = {}
    for stack, _, _, dur, _ in leaf(ops):
        st = stage_of(stack, names, first)
        out[st] = out.get(st, 0.0) + dur
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def by_program(ops: Iterable[Op]) -> Dict[int, List[Op]]:
    out: Dict[int, List[Op]] = {}
    for op in ops:
        out.setdefault(op[1], []).append(op)
    return out


def unscoped_top(ops: Iterable[Op], names: Iterable[str], top: int = 8
                 ) -> List[List]:
    """``[[short name, name stack, ns], ...]`` of the largest operations
    under no stage: what a scope is still missing around."""
    names = frozenset(names)
    tot: Dict[Tuple[str, str], float] = {}
    for stack, _, _, dur, text in leaf(ops):
        if stage_of(stack, names) == UNSCOPED:
            key = (trace.short_name(text), stack)
            tot[key] = tot.get(key, 0.0) + dur
    return [[k[0], k[1], v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def top_ops_by_stage(ops: Iterable[Op], names: Iterable[str], top: int = 10
                     ) -> List[List]:
    """``[[short name, ns, {stage: ns}], ...]``: the operations as
    ``lib.trace.reduce_planes`` keys its ``top_ops`` (instruction name and
    opcode, the same name in two programs added up), each with the stages
    its time belongs to, so that a ledger line's ``fusion.800`` can be
    given one."""
    names = frozenset(names)
    tot: Dict[str, Dict[str, float]] = {}
    for stack, _, _, dur, text in leaf(ops):
        d = tot.setdefault(trace.short_name(text), {})
        st = stage_of(stack, names)
        d[st] = d.get(st, 0.0) + dur
    ranked = sorted(tot.items(), key=lambda kv: -sum(kv[1].values()))[:top]
    return [[k, sum(d.values()),
             dict(sorted(d.items(), key=lambda kv: -kv[1]))]
            for k, d in ranked]


# -- what a program's runs carried -------------------------------------------
def label_runs(runs: Sequence[RunOf],
               steps: Sequence[Tuple[float, int, int]]
               ) -> Dict[int, List[Tuple[int, int]]]:
    """``{program id: [(tokens, prefill_tokens), ...]}``, one entry a run
    that a step can be put to.  ``steps``: ``(start, tokens,
    prefill_tokens)`` of the ``v2.schedule`` spans ON THE RUNS' CLOCK.  A
    step's program is dispatched after its schedule span began and the
    step waits for it before the next is scheduled, so a run belongs to
    the last schedule span that began before it did; a run before the
    first has none."""
    steps = sorted(steps)
    starts = [s for s, _, _ in steps]
    out: Dict[int, List[Tuple[int, int]]] = {}
    for pid, _, t0, _, _ in runs:
        i = bisect.bisect_right(starts, t0) - 1
        if i >= 0:
            out.setdefault(pid, []).append((steps[i][1], steps[i][2]))
    return out


def step_kind(carried: Sequence[Tuple[int, int]]) -> str:
    """``decode<=16`` / ``chunk<=1024`` / ``mixed<=16``: whether none,
    all or some of the program's runs carried prompt tokens (the smallest
    token bucket serves the decode steps and a prompt's last few rows
    beside them), and the most rows one run carried (the bucket's fill);
    ``""`` for a program no step was put to."""
    if not carried:
        return ""
    prefill = sum(1 for _, p in carried if p > 0)
    kind = ("decode" if not prefill else
            "chunk" if prefill == len(carried) else "mixed")
    return f"{kind}<={max(t for t, _ in carried)}"


# -- fusions that mix stages ---------------------------------------------------
def mixed_fusions(fusions: Dict[Tuple[int, str], Set[str]],
                  ops: Iterable[Op]) -> Dict[str, float]:
    """``fusions``: ``{(program id, instruction name): stages of the fused
    computation's instructions}``.  A fusion is ONE operation on the
    timeline and carries its root's name stack, so where the compiler
    fused another stage's work in, that work counts to the root's stage.
    ``mixed``: fusions whose instructions carry more than one stage (of
    ``fusions`` in all; ``UNSCOPED`` and ``LOOP`` are not counted as
    one); ``mixed_ns``: the time of the traced operations that are such
    fusions, an upper bound of what is counted to a wrong stage (the whole
    fusion's time, of which the foreign part is some)."""
    mixed = {k for k, st in fusions.items()
             if len(st - {UNSCOPED, LOOP}) > 1}
    ns = 0.0
    for _, pid, _, dur, text in leaf(ops):
        name = text.partition(" = ")[0].lstrip("%").strip()
        if (pid, name) in mixed:
            ns += dur
    return {"fusions": len(fusions), "mixed": len(mixed), "mixed_ns": ns}


# -- one capture ---------------------------------------------------------------
@dataclass
class StagePlane:
    name: str
    ops: List[Op] = field(default_factory=list)
    runs: List[RunOf] = field(default_factory=list)


@dataclass
class StageCapture:
    planes: List[StagePlane]
    # program id -> serialized HloProto
    programs: Dict[int, bytes] = field(default_factory=dict)


def _stats(plane, md) -> Dict[str, object]:
    out = {}
    for st in md.stats:
        kind = st.WhichOneof("value")
        value = getattr(st, kind)
        if kind == "ref_value":
            value = plane.stat_metadata[value].name
        out[plane.stat_metadata[st.metadata_id].name] = value
    return out


def load_stage_capture(path: str) -> StageCapture:
    """The device planes' operations with their name stacks and program
    ids, the program runs, and the programs' ``HloProto`` bytes."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    cap = StageCapture(planes=[])
    for plane in xs.planes:
        if plane.name == "/host:metadata":
            for mid, md in plane.event_metadata.items():
                blob = _stats(plane, md).get("Hlo Proto")
                if isinstance(blob, bytes):
                    cap.programs[int(mid)] = blob
            continue
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        sp = StagePlane(plane.name)
        facts: Dict[int, Tuple[str, int]] = {}
        for line in plane.lines:
            base = line.timestamp_ns
            if line.name == trace.OPS_LINE:
                for ev in line.events:
                    md = plane.event_metadata[ev.metadata_id]
                    if ev.metadata_id not in facts:
                        st = _stats(plane, md)
                        stack = st.get("tf_op", "")
                        if isinstance(stack, bytes):
                            stack = stack.decode(errors="replace")
                        facts[ev.metadata_id] = (
                            str(stack), int(st.get("program_id", 0)))
                    stack, pid = facts[ev.metadata_id]
                    sp.ops.append((stack, pid, base + ev.offset_ps / 1e3,
                                   ev.duration_ps / 1e3, md.name))
            elif line.name == trace.MODULES_LINE:
                for ev in line.events:
                    md = plane.event_metadata[ev.metadata_id]
                    m = _PROGRAM.match(md.name)
                    run_id = -1
                    for st in ev.stats:
                        if plane.stat_metadata[st.metadata_id].name \
                                == "run_id":
                            run_id = int(getattr(st,
                                                 st.WhichOneof("value")))
                    t0 = base + ev.offset_ps / 1e3
                    sp.runs.append((int(m.group(2)) if m else 0, run_id, t0,
                                    t0 + ev.duration_ps / 1e3,
                                    m.group(1) if m else md.name))
        cap.planes.append(sp)
    return cap


def mixed_fusions_of(cap: StageCapture, names: Iterable[str]
                     ) -> Optional[Dict[Tuple[int, str], Set[str]]]:
    """``{(program id, fusion's name): stages of its instructions}`` from
    the capture's own programs; None where the capture holds none or the
    proto that reads them is not installed."""
    if not cap.programs:
        return None
    try:
        from tensorflow.compiler.xla.service import hlo_pb2
    except ImportError:
        return None
    names = frozenset(names)
    out: Dict[Tuple[int, str], Set[str]] = {}
    for pid, blob in cap.programs.items():
        proto = hlo_pb2.HloProto()
        proto.ParseFromString(blob)
        comps = {c.id: c for c in proto.hlo_module.computations}
        for comp in comps.values():
            for ins in comp.instructions:
                if ins.opcode != "fusion" or not ins.called_computation_ids:
                    continue
                body = comps.get(ins.called_computation_ids[0])
                if body is None:
                    continue
                out[(pid, ins.name)] = {
                    stage_of(i.metadata.op_name, names)
                    for i in body.instructions if i.metadata.op_name}
    return out


def stage_tables(cap: StageCapture, names: Sequence[str],
                 steps_by_plane: Optional[Dict[str, Sequence[
                     Tuple[float, int, int]]]] = None, top: int = 12,
                 top_ops: int = 40) -> dict:
    """``breakdown.stages`` of one capture.  Seconds are per chip (means
    over the chips that ran anything); a share is of the leaf
    operations' summed time.  ``steps_by_plane``: for each device plane,
    the ``v2.schedule`` spans on that plane's clock (``label_runs``);
    without them a program has no step kind (a train cell's has none to
    have).  ``device_ops``: the ``top_ops`` largest operations with their
    stages, four times what a ledger line lists, since another window of
    the same cell runs other programs and ranks other names first."""
    planes = [p for p in cap.planes if p.ops]
    if not planes:
        raise ValueError("the capture holds no device operation")
    n, ns = len(planes), 1e-9
    names = tuple(names)
    ops = [op for p in planes for op in p.ops]
    total = sum(op[3] for op in leaf(ops))

    def table(d):
        return {k: [v / n * ns, v / total if total else 0.0]
                for k, v in d.items()}

    inner = by_stage(ops, names)
    out = {
        "chips": n, "ops_s": total / n * ns,
        "stages": table(inner), "outer": table(by_stage(ops, names, True)),
        "unscoped_share": inner.get(UNSCOPED, 0.0) / total if total else 0.0,
        "unscoped_top": [[a, b, v / n * ns]
                         for a, b, v in unscoped_top(ops, names)],
        "device_ops": [[k, v / n * ns, {st: x / n * ns
                                        for st, x in d.items()}]
                       for k, v, d in top_ops_by_stage(ops, names, top_ops)],
        "vocabulary": len(names)}
    fusions = mixed_fusions_of(cap, names)
    if fusions is not None:
        mixed = mixed_fusions(fusions, ops)
        out["mixed_fusions"] = {
            "fusions": mixed["fusions"], "mixed": mixed["mixed"],
            "mixed_s": mixed["mixed_ns"] / n * ns,
            "mixed_share": mixed["mixed_ns"] / total if total else 0.0}
    carried: Dict[int, List[Tuple[int, int]]] = {}
    runs: Dict[int, List[RunOf]] = {}
    for p in planes:
        for r in p.runs:
            runs.setdefault(r[0], []).append(r)
        for pid, got in label_runs(
                p.runs, (steps_by_plane or {}).get(p.name, ())).items():
            carried.setdefault(pid, []).extend(got)
    programs = []
    for pid, pops in by_program(ops).items():
        ptotal = sum(op[3] for op in leaf(pops))
        pruns = runs.get(pid, [])
        got = carried.get(pid, [])
        programs.append({
            "program": pruns[0][4] if pruns else "", "program_id": str(pid),
            "kind": step_kind(got), "runs": len(pruns) / n,
            "tokens_p50": statistics.median(t for t, _ in got) if got
            else None,
            "prefill_runs": sum(1 for _, p in got if p > 0) / n,
            "mean_run_ms": (sum(r[3] - r[2] for r in pruns) / len(pruns)
                            * 1e-6 if pruns else None),
            "ops_s": ptotal / n * ns,
            "share": ptotal / total if total else 0.0,
            "stages": {k: [v / n * ns, v / ptotal if ptotal else 0.0]
                       for k, v in by_stage(pops, names).items()}})
    programs.sort(key=lambda p: -p["ops_s"])
    out["programs"] = programs[:top]
    return out
