"""Static audit of planner output: ``graft_lint --plan``.

A plan entry is only "load-ready" if the config it pins actually lowers
clean — so for each registered bench-row query, compile the plan, take
the TOP-ranKed fragment, scale it onto the tiny-geometry 8-device twin
(same discipline as analysis/targets.py: the audit checks graph
structure, not byte volumes), and run the graph + memory-plan audits
over one shared lowering.  A top-ranked config that fails its own
static audit is a planner bug and must fail the lint, not ship in a
plan file.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, Optional, Tuple

from deepspeed_tpu.planner.rank import Plan, compile_plan
from deepspeed_tpu.planner.space import FleetSpec, ModelSpec

# bench rows with a pinned known-good config whose planner query is
# re-auditable offline (the regression gate in tests/test_planner.py
# asserts top-3 rank against these same queries)
PLAN_AUDIT_ROWS = ("gpt2_350m", "gpt2_350m_commquant",
                   "gpt2_350m_autosched", "longseq_ring")

# the known-good pinned config of each PLAN_AUDIT_ROWS query at the
# canonical 8-chip fleet (the regression gate asserts each ranks top-3),
# plus the 6.7B offload rung the planner must propose sight-unseen
PINNED_CONFIGS = {
    "gpt2_350m": {
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 1},
    },
    "gpt2_350m_commquant": {
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 1},
        "comm_quantization": {"enabled": True, "grad_reduce": "int8"},
    },
    "gpt2_350m_autosched": {
        "mesh": {"data": 8},
        "zero_optimization": {"stage": 3},
        "step_schedule": {"mode": "pinned", "gather_prefetch_depth": 2,
                          "param_persistence_threshold": 100_000},
    },
    "longseq_ring": {
        "mesh": {"seq": 8},
        "zero_optimization": {"stage": 2},
    },
    # streamed host params + chunked NVMe optimizer
    "gpt2_6_7b_chunked": {
        "mesh": {"data": 1},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu"},
            "offload_optimizer": {"device": "nvme",
                                  "working_set_bytes": 1 << 30,
                                  "chunk_bytes": 64 << 20}},
    },
}


def plan_for_row(name: str, chips: int = 8, *,
                 top: Optional[int] = 10) -> Plan:
    """The planner query mirroring a bench row's config space: same
    model class and fleet shape, constrained the way the row's
    experiment is (the autosched row studies stage-3 scheduling, the
    commquant row enables the quantized wire, ...)."""
    fleet = FleetSpec(chips=chips)
    if name == "gpt2_350m":
        model = ModelSpec.from_name("gpt2-350m", seq_len=1024)
        return compile_plan(model, fleet, enable_quant=False,
                            max_micro_batch=16, top=top)
    if name == "gpt2_350m_commquant":
        model = ModelSpec.from_name("gpt2-350m", seq_len=1024)
        return compile_plan(model, fleet, enable_quant=True,
                            max_micro_batch=16, top=top)
    if name == "gpt2_350m_autosched":
        model = ModelSpec.from_name("gpt2-350m", seq_len=1024)
        return compile_plan(model, fleet, stages=(3,),
                            enable_quant=False, max_micro_batch=16,
                            top=top)
    if name == "longseq_ring":
        model = ModelSpec.from_name(
            "llama3-8b", seq_len=32768, hidden_size=2048, num_heads=16,
            num_kv_heads=8, intermediate_size=8192, num_layers=6,
            vocab_size=32256, max_seq_len=32768, seq_impl="ring")
        # the row shards the sequence over EVERY chip (mesh {"seq": n})
        # — the planner ranks stage/schedule within that placement family
        return compile_plan(model, fleet, enable_quant=False,
                            enable_offload=False, max_micro_batch=4,
                            top=top,
                            mesh_filter=lambda m: m.get("seq", 1) == chips)
    raise KeyError(f"unknown plan audit row {name!r} "
                   f"(known: {list(PLAN_AUDIT_ROWS)})")


def _scale_mesh(mesh: Dict[str, int], cfg, n: int) -> Dict[str, int]:
    """Clamp a planned mesh onto the twin model's divisibility (tiny
    head/layer counts) while keeping the device product at ``n``."""
    heads = cfg.num_heads
    kv = cfg.num_kv_heads or heads
    layers = cfg.num_layers
    experts = getattr(cfg, "num_experts", 0) or 0
    tp = int(mesh.get("tensor", 1))
    while tp > 1 and (heads % tp or kv % tp):
        tp //= 2
    sp = int(mesh.get("seq", 1))
    while sp > 1 and heads % sp:
        sp //= 2
    pp = int(mesh.get("pipe", 1))
    while pp > 1 and layers % pp:
        pp //= 2
    ep = int(mesh.get("expert", 1))
    while ep > 1 and (not experts or experts % ep):
        ep //= 2
    mp = tp * sp * pp * ep
    while mp > 1 and n % mp:
        # shave the largest axis until the product divides the mesh
        biggest = max(("tensor", tp), ("seq", sp), ("pipe", pp),
                      ("expert", ep), key=lambda t: t[1])[0]
        if biggest == "tensor":
            tp //= 2
        elif biggest == "seq":
            sp //= 2
        elif biggest == "pipe":
            pp //= 2
        else:
            ep //= 2
        mp = tp * sp * pp * ep
    out = {"data": max(1, n // mp)}
    for k, v in (("tensor", tp), ("pipe", pp), ("seq", sp),
                 ("expert", ep)):
        if v > 1:
            out[k] = v
    return out


def prepared_plan_target(name: str):
    """(PreparedTarget, fragment): the row's top-ranked plan fragment
    applied to the tiny twin geometry, engine built and ready to lower."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.targets import _prep_engine
    from deepspeed_tpu.models import get_model_config

    plan = plan_for_row(name)
    if not plan.ranked:
        raise RuntimeError(f"plan for {name} ranked no candidates")
    frag = copy.deepcopy(plan.ranked[0].config)
    n = jax.device_count()
    if name == "longseq_ring":
        twin = get_model_config("llama-tiny", max_seq_len=128,
                                seq_impl="ring",
                                ring_placement="striped",
                                attn_impl="xla")
    else:
        twin = get_model_config("gpt2-tiny", max_seq_len=64)
    cfg = dict(frag)
    cfg["train_micro_batch_size_per_gpu"] = 1
    cfg["gradient_accumulation_steps"] = 2
    cfg["mesh"] = _scale_mesh(frag.get("mesh") or {"data": n}, twin, n)
    cfg["gradient_clipping"] = 1.0
    cfg["steps_per_print"] = 10_000
    engine, _, _, _ = ds.initialize(model=twin, config=cfg)
    return _prep_engine(engine, f"plan:{name}"), frag


def audit_planned_config(name: str, budget: Optional[int] = None
                         ) -> Tuple[Dict[str, Any], Any, Any]:
    """Lower the row's top-ranked plan twin once and run both audit
    families → (fragment, GraphAuditReport, MemoryAuditReport)."""
    from deepspeed_tpu.analysis.auditor import audit_artifacts, lower_step
    from deepspeed_tpu.analysis.memory import audit_memory

    prep, frag = prepared_plan_target(name)
    try:
        art = lower_step(prep.fn, *prep.args, label=prep.label)
    finally:
        prep.cleanup()
    graph = audit_artifacts(art, intent=prep.intent)
    mem = audit_memory(art, intent=prep.memory_intent, budget=budget)
    return frag, graph, mem
