"""Static graph auditor: lower a jitted step, never run it, name defects.

Three check families, all read off artifacts that exist *before* any
step executes:

* **Collective census** (post-SPMD HLO, ``analysis/hlo.py``): every
  all-gather / all-reduce / reduce-scatter / collective-permute /
  all-to-all with wire dtype and modeled wire bytes, diffed against the
  :class:`AuditIntent` derived from the config — a GSPMD-inserted
  resharding nobody declared or an fp32 wire on a quantized path is a
  named high-severity finding.
* **Donation audit** (the module header's ``input_output_alias`` map vs
  the ``donate_argnums`` the caller declared): a donated buffer XLA
  could not alias stays live across the step and inflates peak HBM by
  its full footprint.
* **Hot-path hygiene** (the jaxpr + args signature): host callbacks
  inside the step, bf16→fp32 promotions in low-precision compute, and
  recompile hazards (python scalars / weak-type constants) that make the
  jit cache miss on value instead of shape.

The auditor costs one AOT ``lower().compile()`` — the same one-time
price ``profiling/flops_profiler.profile_compiled`` already pays — and
zero step executions, so it runs on the virtual 8-device CPU mesh in CI
against every bench-row step config (``analysis/targets.py``).
"""

from __future__ import annotations

import re
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.analysis.hlo import (aggregate_census,
                                        entry_parameters, has_infeed,
                                        parse_collectives,
                                        parse_input_output_alias)
from deepspeed_tpu.analysis.report import (Finding, GraphAuditReport)

# jaxpr primitives that round-trip through the host mid-step.  A step
# containing one serializes device execution behind python; only the
# debug prims (jax.debug.callback / jax.debug.print) degrade to a
# warning — they are at least async — everything else is a high finding.
_DEBUG_CALLBACK_PRIMS = ("debug_callback", "debug_print")
HOST_CALLBACK_PRIMS = ("callback", "io_callback", "outside_call",
                       "pure_callback") + _DEBUG_CALLBACK_PRIMS

# post-lowering spellings of the same defect
_CALLBACK_CUSTOM_CALLS = ("xla_python_cpu_callback",
                          "xla_python_gpu_callback",
                          "xla_ffi_python_cpu_callback")

_LOW_PRECISION = ("bfloat16", "float16")


@dataclass
class AuditIntent:
    """Declared communication/compute intent the census is diffed against.

    ``expected``: collective kinds the config explains — any OTHER kind
    carrying ≥ ``min_unexpected_bytes`` is an ``implicit_resharding``.
    ``required``: ``{kind: (wire dtypes,)}`` that MUST appear (empty
    tuple = any dtype) — e.g. a quantized grad reduce must surface an
    int8 ``all-to-all``; absence is a ``collective_mismatch``.
    ``banned``: ``{kind: (wire dtypes,)}`` that must NOT appear at
    volume — an fp32 ``all-reduce`` on a path whose reduce was declared
    quantized is a ``wire_dtype_mismatch``.
    """
    expected: frozenset = frozenset()
    required: Dict[str, Tuple[str, ...]] = field(default_factory=dict)
    banned: Dict[str, Tuple[str, ...]] = field(default_factory=dict)
    compute_dtype: str = "fp32"
    min_unexpected_bytes: int = 1 << 16
    allow_callbacks: bool = False


# ----------------------------------------------------------------------
# jaxpr-level checks
# ----------------------------------------------------------------------
def _iter_eqns(jaxpr):
    """Depth-first over every equation, descending into sub-jaxprs
    (scan bodies, cond branches, custom_vjp calls, pjit) duck-typed —
    no jax-internal imports (the seam lint applies to this file too)."""
    stack = [jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            for val in eqn.params.values():
                stack.extend(_subjaxprs(val))


def _subjaxprs(val):
    out = []
    if hasattr(val, "eqns"):
        out.append(val)
    elif hasattr(val, "jaxpr") and hasattr(val.jaxpr, "eqns"):
        out.append(val.jaxpr)
    elif isinstance(val, (list, tuple)):
        for v in val:
            out.extend(_subjaxprs(v))
    return out


def _callback_findings(jaxpr, label: str) -> List[Finding]:
    hits: Dict[str, int] = {}
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in HOST_CALLBACK_PRIMS or name in ("infeed", "outfeed"):
            hits[name] = hits.get(name, 0) + 1
    return [
        Finding(
            kind="host_callback",
            severity="warning" if prim in _DEBUG_CALLBACK_PRIMS else "high",
            message=f"{count}× `{prim}` inside the compiled step — every "
                    "call is a device→host→device round trip on the hot "
                    "path",
            where=label, detail={"key": prim, "count": count})
        for prim, count in sorted(hits.items())
    ]


def _promotion_findings(jaxpr, label: str, compute_dtype: str,
                        min_bytes: int = 1 << 12) -> List[Finding]:
    """bf16/fp16 → fp32 ``convert_element_type`` volume inside a
    low-precision step.  fp32 accumulation is often deliberate (softmax,
    loss, grad accumulators), so this aggregates to ONE finding and only
    escalates info→warning above 16 MiB of promoted output."""
    if compute_dtype not in ("bf16", "fp16"):
        return []
    count, total = 0, 0
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        try:
            src = str(eqn.invars[0].aval.dtype)
            out = eqn.outvars[0].aval
        except (AttributeError, IndexError):
            continue
        if src in _LOW_PRECISION and str(out.dtype) == "float32":
            nbytes = int(out.size) * 4
            if nbytes >= min_bytes:
                count += 1
                total += nbytes
    if not count:
        return []
    return [Finding(
        kind="dtype_promotion",
        severity="warning" if total >= (1 << 24) else "info",
        message=f"{count} fp32 promotions of {_LOW_PRECISION[0]}/"
                f"{_LOW_PRECISION[1]} tensors ({total} output bytes) in a "
                f"{compute_dtype} step — check each is a deliberate "
                "accumulator, not a leaked upcast",
        where=label, detail={"key": "bf16->f32", "count": count,
                             "bytes": total})]


def _signature_findings(args, label: str) -> List[Finding]:
    """Recompile hazards in the example arguments: python scalars trace
    as weak-type *constants* (a new value = a new program), and
    weak-type arrays re-specialize the jit cache the same way."""
    import jax

    hazards: List[Tuple[str, str]] = []

    def visit(path, leaf):
        if isinstance(leaf, (bool, int, float)):
            hazards.append((jax.tree_util.keystr(path),
                            f"python {type(leaf).__name__}"))
        elif getattr(leaf, "weak_type", False):
            hazards.append((jax.tree_util.keystr(path), "weak-type array"))

    jax.tree_util.tree_map_with_path(visit, args)
    return [Finding(
        kind="recompile_hazard", severity="warning",
        message=f"step argument {path or '<root>'} is a {what}: its "
                "VALUE is baked into the trace, so every new value "
                "recompiles the step",
        where=label, detail={"key": path, "what": what})
        for path, what in hazards]


# ----------------------------------------------------------------------
# HLO-level checks
# ----------------------------------------------------------------------
def _census_findings(census, intent: AuditIntent,
                     label: str) -> List[Finding]:
    findings: List[Finding] = []
    present: Dict[str, set] = {}
    for row in census:
        present.setdefault(row.kind, set()).update(
            row.dtype.split("+"))
        key = f"{row.kind}:{row.dtype}"
        if (row.kind not in intent.expected
                and row.payload_bytes >= intent.min_unexpected_bytes):
            findings.append(Finding(
                kind="implicit_resharding", severity="high",
                message=f"{row.count}× {row.kind} ({row.dtype}, "
                        f"{row.payload_bytes} payload bytes) in the "
                        "lowered step but the config declares no source "
                        "for it — GSPMD inserted a resharding nobody "
                        "asked for",
                where=label, detail={"key": key, "count": row.count,
                                     "payload_bytes": row.payload_bytes,
                                     "wire_bytes": row.wire_bytes}))
        banned = intent.banned.get(row.kind)
        if (banned and row.payload_bytes >= intent.min_unexpected_bytes
                and any(d in banned for d in row.dtype.split("+"))):
            findings.append(Finding(
                kind="wire_dtype_mismatch", severity="high",
                message=f"{row.kind} moves {row.dtype} "
                        f"({row.payload_bytes} payload bytes) on a path "
                        "the config declares quantized — the wire dtype "
                        "never narrowed",
                where=label, detail={"key": f"banned:{key}",
                                     "payload_bytes": row.payload_bytes}))
    for kind, dtypes in sorted(intent.required.items()):
        have = present.get(kind, set())
        if not have or (dtypes and not have.intersection(dtypes)):
            findings.append(Finding(
                kind="collective_mismatch", severity="warning",
                message=f"config declares a {kind} "
                        f"({'/'.join(dtypes) or 'any dtype'}) but the "
                        f"lowered step contains "
                        f"{'none' if not have else 'only ' + '/'.join(sorted(have))}"
                        " — the declared comm path did not materialize",
                where=label, detail={"key": f"required:{kind}"}))
    return findings


def _donation_audit(flat_args_info, hlo_text: str, label: str,
                    min_high_bytes: int = 1 << 16
                    ) -> Tuple[Dict[str, Any], List[Finding]]:
    donated = [i for i, a in enumerate(flat_args_info)
               if getattr(a, "donated", False)]
    alias = parse_input_output_alias(hlo_text)
    entry = entry_parameters(hlo_text)
    reliable = len(entry) == len(flat_args_info)
    aliased = [i for i in donated if i in alias] if reliable \
        else sorted(alias)
    block: Dict[str, Any] = {"declared": len(donated),
                             "aliased": len(aliased), "missed": [],
                             "missed_bytes": 0}
    findings: List[Finding] = []
    if not donated:
        return block, findings
    if not reliable:
        # unused args were dropped from the executable: indices no longer
        # line up, so report counts only (never a phantom per-buffer miss)
        gap = max(0, len(donated) - len(alias))
        block["missed_bytes"] = -1 if gap else 0
        if gap:
            findings.append(Finding(
                kind="donation_miss", severity="warning",
                message=f"{gap} of {len(donated)} donated buffers have no "
                        "output alias (parameter indices unmappable: the "
                        "executable dropped unused args)",
                where=label, detail={"key": "unmapped", "gap": gap}))
        return block, findings
    groups: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for i in donated:
        if i in alias:
            continue
        a = flat_args_info[i]
        shape = tuple(getattr(a, "shape", ()))
        dtype = str(getattr(a, "dtype", "?"))
        try:
            import numpy as np
            nbytes = int(np.prod(shape or (1,))) * np.dtype(dtype).itemsize
        except Exception:
            nbytes = 0
        block["missed"].append({"param_index": i, "shape": list(shape),
                                "dtype": dtype, "bytes": nbytes})
        block["missed_bytes"] += nbytes
        g = groups.setdefault((str(shape), dtype),
                              {"count": 0, "bytes": 0, "indices": []})
        g["count"] += 1
        g["bytes"] += nbytes
        g["indices"].append(i)
    for (shape, dtype), g in sorted(groups.items(),
                                    key=lambda kv: -kv[1]["bytes"]):
        sev = ("high" if g["bytes"] >= min_high_bytes
               else "warning" if g["bytes"] >= 1024 else "info")
        findings.append(Finding(
            kind="donation_miss", severity=sev,
            message=f"{g['count']}× donated {dtype}{shape} "
                    f"({g['bytes']} bytes) not aliased to any output — "
                    "the buffer stays live across the step and inflates "
                    "peak HBM by its full footprint",
            where=label,
            detail={"key": f"{shape}:{dtype}", "count": g["count"],
                    "bytes": g["bytes"],
                    "param_indices": g["indices"][:8]}))
    return block, findings


# ----------------------------------------------------------------------
# the auditor
# ----------------------------------------------------------------------
@dataclass
class LoweredStep:
    """One AOT lowering's reusable artifacts.

    Every audit family (collective census, donation, memory plan) reads
    off the same trio — jaxpr, lowered, compiled — so a caller auditing
    one target several ways pays the ~2s trace+lower+compile ONCE
    (``analysis/targets.py`` / ``graft_lint --rows --memory``) instead of
    once per audit.  The artifacts stay valid after the owning engine is
    destroyed: they are standalone AOT objects, and the audits only read
    text/metadata off them."""
    label: str
    jaxpr: Any
    lowered: Any
    compiled: Any
    hlo: str
    args: Tuple[Any, ...]
    backend: str
    num_partitions: int


def lower_step(fn, *args, label: str = "step",
               static_kwargs: Optional[Dict[str, Any]] = None
               ) -> LoweredStep:
    """Trace + lower + AOT-compile one jitted function (shapes only —
    NEVER executed, so zero-filled arrays are fine and donated example
    buffers are not consumed) into a reusable :class:`LoweredStep`."""
    import jax

    kw = static_kwargs or {}
    if not hasattr(fn, "lower"):
        raise TypeError(f"audit needs a jax.jit-wrapped callable, got "
                        f"{type(fn).__name__} (wrap it in jax.jit first)")
    with warnings.catch_warnings():
        # jax's donated-buffers-not-usable warning (raised at lowering)
        # is OUR report — do not also print it
        warnings.simplefilter("ignore")
        if hasattr(fn, "trace"):
            traced = fn.trace(*args, **kw)
            jaxpr = traced.jaxpr
            lowered = traced.lower()   # one trace serves both artifacts
        else:  # pragma: no cover - older jax without AOT trace()
            jaxpr = jax.make_jaxpr(fn)(*args, **kw).jaxpr
            lowered = fn.lower(*args, **kw)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    # SPMD modules always carry num_partitions= in the header; absence
    # means a single-partition program, so the fallback is 1 (never the
    # host's device count — a single-device jit on an 8-device host
    # must not have its wire model scaled by 8)
    m = re.search(r"num_partitions=(\d+)", hlo)
    return LoweredStep(label=label, jaxpr=jaxpr, lowered=lowered,
                       compiled=compiled, hlo=hlo, args=tuple(args),
                       backend=jax.default_backend(),
                       num_partitions=int(m.group(1)) if m else 1)


def audit(fn, *args, label: str = "step", intent: Optional[AuditIntent] = None,
          static_kwargs: Optional[Dict[str, Any]] = None
          ) -> GraphAuditReport:
    """Audit one jitted function against example ``args`` (lower + audit
    in one call; use :func:`lower_step` + :func:`audit_artifacts` to
    share the lowering with the memory auditor)."""
    return audit_artifacts(lower_step(fn, *args, label=label,
                                      static_kwargs=static_kwargs),
                           intent=intent)


def audit_artifacts(art: LoweredStep,
                    intent: Optional[AuditIntent] = None
                    ) -> GraphAuditReport:
    """The graph audit proper, off pre-lowered artifacts."""
    import jax

    intent = intent or AuditIntent()
    label = art.label
    jaxpr, lowered, hlo = art.jaxpr, art.lowered, art.hlo
    num_partitions = art.num_partitions
    args = art.args
    findings: List[Finding] = []
    if not intent.allow_callbacks:
        findings.extend(_callback_findings(jaxpr, label))
    findings.extend(_promotion_findings(jaxpr, label,
                                        intent.compute_dtype))
    findings.extend(_signature_findings(args, label))

    ops = parse_collectives(hlo, num_partitions=num_partitions)
    census = aggregate_census(ops)
    findings.extend(_census_findings(census, intent, label))

    flat_info, _ = jax.tree_util.tree_flatten(lowered.args_info)
    donation, don_findings = _donation_audit(flat_info, hlo, label)
    findings.extend(don_findings)

    if not intent.allow_callbacks:
        # post-lowering catch for callbacks the jaxpr walk missed (e.g.
        # injected by a custom lowering rule).  Every jaxpr callback
        # prim (debug_callback included) lowers to the same custom-call
        # targets, so attribution is by COUNT: more callback sites in
        # the HLO than jaxpr hits means lowering added some.  Warning,
        # not high — loop unrolling can legitimately duplicate one
        # jaxpr-level site into several HLO sites.
        jaxpr_cb = sum(int(f.detail.get("count", 1)) for f in findings
                       if f.kind == "host_callback")
        hlo_cb = sum(hlo.count(f'custom_call_target="{t}"')
                     for t in _CALLBACK_CUSTOM_CALLS)
        if hlo_cb > jaxpr_cb:
            findings.append(Finding(
                kind="host_callback",
                severity="high" if jaxpr_cb == 0 else "warning",
                message=f"{hlo_cb} callback custom-call(s) in the "
                        f"optimized HLO vs {jaxpr_cb} jaxpr-level "
                        "callback(s) — a host round trip was injected "
                        "below the jaxpr (custom lowering rule?)",
                where=label, detail={"key": "lowered_callback",
                                     "hlo_sites": hlo_cb,
                                     "jaxpr_sites": jaxpr_cb}))
        known = {f.detail.get("key") for f in findings
                 if f.kind == "host_callback"}
        if has_infeed(hlo) and "infeed" not in known:
            findings.append(Finding(
                kind="host_callback", severity="high",
                message="infeed op in the optimized HLO",
                where=label, detail={"key": "infeed"}))

    order = {"high": 0, "warning": 1, "info": 2}
    findings.sort(key=lambda f: (order[f.severity], f.kind,
                                 str(f.detail.get("key", ""))))
    return GraphAuditReport(
        label=label, backend=art.backend,
        num_partitions=max(1, num_partitions), census=census,
        donation=donation, findings=findings)


# ----------------------------------------------------------------------
# config → intent, engine adapters
# ----------------------------------------------------------------------
def intent_for_engine(engine) -> AuditIntent:
    """Derive the declared comm/compute intent from a built
    ``GraftEngine``: mesh axes + ZeRO stage + ``comm_quantization`` +
    ``step_schedule`` explain which collective kinds may appear."""
    topo = engine.topology
    cfg = engine.config
    stage = engine.zero_stage
    dp = getattr(topo, "dp_size", 1)
    tp = getattr(topo, "tp_size", 1)
    pp = getattr(topo, "pp_size", 1)
    sp = getattr(topo, "sp_size", 1)
    ep = getattr(topo, "ep_size", 1)

    expected = set()
    required: Dict[str, Tuple[str, ...]] = {}
    banned: Dict[str, Tuple[str, ...]] = {}
    if dp > 1:
        expected.add("all-reduce")
        if stage >= 1 or cfg.step_schedule.weight_update == "decomposed":
            # sharded optimizer state makes XLA free to express the
            # reduce as reduce-scatter + re-gather of updated params,
            # and the declared grad-accumulator sharding constraint
            # legitimately reshards batch-parallel gradients into the
            # ZeRO layout (an all-to-all per GSPMD) — those layout
            # transitions are the config's own intent, not implicit
            expected.update(("all-gather", "reduce-scatter",
                             "all-to-all"))
    if tp > 1:
        expected.update(("all-reduce", "all-gather", "reduce-scatter"))
        if dp > 1 and stage >= 1:
            # 2-D dp×tp mesh with a sharded optimizer: the layout
            # transition between batch-parallel gradients and the
            # (data, tensor)-factored ZeRO state legitimately lowers as
            # collective-permutes (GSPMD routes the cross-axis reshard
            # point-to-point; observed on the train_resumed target's
            # data×tensor resume mesh — identical on a from-scratch
            # engine with the same mesh, so it is the config's own
            # intent, not a resume artifact)
            expected.add("collective-permute")
    if pp > 1:
        expected.update(("collective-permute", "all-reduce", "all-gather"))
    if sp > 1:
        expected.update(("all-gather", "all-reduce", "reduce-scatter"))
        seq_impl = getattr(engine.model_config, "seq_impl", "") \
            if engine.model_config is not None else ""
        if seq_impl == "ring":
            expected.add("collective-permute")
            required.setdefault("collective-permute", ())
            ring_wire = getattr(engine.model_config, "ring_wire_dtype",
                                "fp32")
            if ring_wire != "fp32":
                # quantized ring rotation (comm_quantization.ring_rotation):
                # the K/V payload moves s8 (int8) or u8 (fp8 bitcast) —
                # a DECLARED narrow wire, not a wire_dtype_mismatch; the
                # fp32-wire rotation's u32 word-packing must be gone
                # (the small fp32 scale messages stay legitimate)
                required["collective-permute"] = ("s8", "u8")
                banned["collective-permute"] = ("u32",)
        else:   # ulysses/alst head<->seq exchanges
            expected.add("all-to-all")
    if ep > 1:
        expected.add("all-to-all")

    cq = getattr(cfg, "comm_quantization", None)
    if cq is not None and getattr(cq, "enabled", False) \
            and getattr(engine, "_comm_quant", None) is not None:
        wire = getattr(cq, "grad_reduce", "fp32")
        if wire in ("int8", "fp8"):
            # quantized reduce = quantize → all-to-all → dequant-reduce;
            # fp8 bitcasts to u8 so every backend moves plain bytes
            expected.add("all-to-all")
            required["all-to-all"] = ("s8", "u8")
            # the GSPMD fp32 grad reduce this path replaces must be gone
            banned["all-reduce"] = ("f32",)
    compute = "bf16" if getattr(cfg, "bf16_enabled", False) else (
        "fp16" if getattr(cfg, "fp16_enabled", False) else "fp32")
    return AuditIntent(expected=frozenset(expected), required=required,
                       banned=banned, compute_dtype=compute)


def audit_engine(engine, data=None, label: str = "train_step"
                 ) -> GraphAuditReport:
    """Audit a built train engine's compiled step without running it."""
    fn, args = engine.audit_step_args(data)
    return audit(fn, *args, label=label, intent=intent_for_engine(engine))


def intent_for_v2(v2) -> AuditIntent:
    """The serving engine's declared collective/dtype intent — shared
    by :func:`audit_v2_engine` and the bench-row target preparer so the
    CLI/tier-1 audits can never drift from the API audit."""
    expected = set()
    if getattr(v2.topology, "tp_size", 1) > 1:
        expected.update(("all-reduce", "all-gather", "reduce-scatter"))
    if getattr(v2.topology, "ep_size", 1) > 1:
        expected.add("all-to-all")
    compute = "bf16" if "bf" in str(v2.cfg.dtype) else "fp32"
    return AuditIntent(expected=frozenset(expected), compute_dtype=compute)


def audit_v2_engine(v2, phase: str = "decode",
                    label: Optional[str] = None) -> GraphAuditReport:
    """Audit the serving engine's ragged prefill/decode step."""
    fn, args = v2.audit_step_args(phase)
    return audit(fn, *args, label=label or f"v2_{phase}",
                 intent=intent_for_v2(v2))


def fused_collective_intent(engine) -> Dict[str, Dict[str, Any]]:
    """Which compute-collective FUSIONS the engine's gates declare —
    the hops that are no longer scheduled around but folded into their
    producing/consuming compute (docs/STATIC_ANALYSIS.md):

    * ``ring_rotation`` — quantized ring wire
      (comm_quantization.ring_rotation; sequence/ring.py): the
      collective-permute payload narrowed + dequant in the flash
      epilogue.
    * ``gather_matmul`` — step_schedule.fused_gather_matmul
      (ops/pallas/gather_matmul.py): MLP param all-gathers issued from
      the matmul region.
    * ``reduce_scatter_epilogue`` — step_schedule.fused_reduce_scatter:
      explicit per-leaf psum_scatter in the grad-accumulator epilogue.
    """
    out: Dict[str, Dict[str, Any]] = {}
    mc = getattr(engine, "model_config", None)
    sp = getattr(engine.topology, "sp_size", 1)
    if (mc is not None and sp > 1
            and getattr(mc, "seq_impl", "") == "ring"
            and getattr(mc, "ring_wire_dtype", "fp32") != "fp32"):
        out["ring_rotation"] = {"kind": "collective-permute",
                                "wire": mc.ring_wire_dtype}
    if mc is not None and getattr(mc, "fused_gather_matmul", False):
        out["gather_matmul"] = {"kind": "all-gather",
                                "axes": list(mc.fused_gather_axes)}
    if getattr(engine, "_fused_rs", False):
        out["reduce_scatter_epilogue"] = {"kind": "reduce-scatter"}
    return out


def collective_census_engine(engine) -> Dict[str, Dict[str, Any]]:
    """Compact census for the overlap scheduler's pinned evidence.

    On top of the per-kind rollup, a ``fused_collective`` entry records
    which hops are FUSED (gate-declared) vs merely scheduled, each with
    ``present`` = whether a matching collective kind materialized in the
    lowered step — so pinned ``static_census`` evidence distinguishes a
    fused wire from a scheduled one."""
    return census_and_memory_engine(engine)[0]


def census_and_memory_engine(engine) -> Tuple[Dict[str, Any],
                                              Optional[Dict[str, Any]]]:
    """Both pinned-evidence blocks off ONE lowering: the collective
    census rollup (``static_census``) and the memory-plan rollup
    (``static_memory``) — the probe pays the AOT trace+lower+compile
    once.  The memory half degrades to None (with a warning) rather than
    costing the probe its census."""
    fn, args = engine.audit_step_args()
    art = lower_step(fn, *args, label="census_probe")
    report = audit_artifacts(art, intent=intent_for_engine(engine))
    summary = report.census_summary()
    fused = fused_collective_intent(engine)
    summary["fused_collective"] = {
        name: {**info, "present": info["kind"] in summary}
        for name, info in sorted(fused.items())}
    static_memory = None
    try:
        from deepspeed_tpu.analysis.memory import (audit_memory,
                                                   memory_intent_for_engine)

        static_memory = audit_memory(
            art, intent=memory_intent_for_engine(engine)).summary()
    except Exception as e:  # census evidence must survive a memory miss
        warnings.warn(f"static memory audit unavailable: {e}")
    return summary, static_memory
