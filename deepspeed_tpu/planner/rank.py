"""Plan compilation: prune → price → rank → emit pinned configs.

The output contract: every ranked entry is a *load-ready*
``DeepSpeedConfig`` fragment (it parses round-trip, see
``runtime.config.load_plan``) carrying its evidence under the frozen
``PLAN_EVIDENCE_KEYS`` — the census rollup it was priced with (anchored
vs extrapolated per row), the calibrated peak prediction, the dominant
cost term, and the overlap credit.  Losers keep their pruning reasons so
a plan file explains the whole space, not just the winners.
"""

from __future__ import annotations

import copy
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.planner.cost import (analytic_census, apply_anchors,
                                        step_time)
from deepspeed_tpu.planner.space import (DEFAULT_CHUNK_BYTES,
                                         DEFAULT_WORKING_SET_BYTES,
                                         Candidate, FleetSpec, ModelSpec,
                                         enumerate_candidates,
                                         prune_candidates)

# every ranked plan entry's evidence dict carries exactly these keys
# (frozen in tools/telemetry_check.py + docs/PLANNER.md)
PLAN_EVIDENCE_KEYS = (
    "census",
    "census_mode",
    "dominant_class",
    "dominant_cost_term",
    "overlap_fraction",
    "predicted_peak_bytes",
    "predicted_step_ms",
    "wire_bytes_total",
)

_TIER_ORDER = {"none": 0, "opt_cpu": 1, "cpu": 2, "cpu_chunked": 3,
               "nvme_chunked": 4, "nvme": 5}


@dataclass
class PlannedConfig:
    rank: int
    candidate: str
    tokens_per_sec_per_chip: float
    config: Dict[str, Any]
    evidence: Dict[str, Any]

    def to_dict(self) -> Dict[str, Any]:
        return {"rank": self.rank, "candidate": self.candidate,
                "tokens_per_sec_per_chip": self.tokens_per_sec_per_chip,
                "config": self.config, "evidence": self.evidence}


@dataclass
class Plan:
    model: str
    seq_len: int
    fleet: Dict[str, Any]
    gas: int
    ranked: List[PlannedConfig] = field(default_factory=list)
    pruned: List[Dict[str, Any]] = field(default_factory=list)
    n_candidates: int = 0

    def to_dict(self) -> Dict[str, Any]:
        return {"model": self.model, "seq_len": self.seq_len,
                "fleet": self.fleet, "gas": self.gas,
                "n_candidates": self.n_candidates,
                "ranked": [r.to_dict() for r in self.ranked],
                "pruned": self.pruned}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Plan":
        plan = cls(model=d["model"], seq_len=d["seq_len"],
                   fleet=dict(d.get("fleet") or {}),
                   gas=int(d.get("gas", 1)),
                   n_candidates=int(d.get("n_candidates", 0)),
                   pruned=list(d.get("pruned") or []))
        for r in d.get("ranked", []):
            plan.ranked.append(PlannedConfig(
                rank=r["rank"], candidate=r["candidate"],
                tokens_per_sec_per_chip=r["tokens_per_sec_per_chip"],
                config=r["config"], evidence=r["evidence"]))
        return plan

    def table(self, top: Optional[int] = None) -> str:
        rows = self.ranked[:top] if top else self.ranked
        lines = [f"plan: {self.model} seq={self.seq_len} "
                 f"chips={self.fleet.get('chips')} "
                 f"({self.n_candidates} candidates, "
                 f"{len(self.pruned)} pruned)",
                 f"{'#':>3} {'tok/s/chip':>12} {'step_ms':>9} "
                 f"{'peak_GiB':>9} {'dominant':>9}  candidate"]
        for r in rows:
            ev = r.evidence
            lines.append(
                f"{r.rank:>3} {r.tokens_per_sec_per_chip:>12.1f} "
                f"{ev['predicted_step_ms']:>9.2f} "
                f"{ev['predicted_peak_bytes'] / (1 << 30):>9.2f} "
                f"{ev['dominant_cost_term']:>9}  {r.candidate}")
        return "\n".join(lines)


def config_fragment(model: ModelSpec, cand: Candidate,
                    gas: int = 1) -> Dict[str, Any]:
    """The pinned, load-ready DeepSpeedConfig fragment for a candidate —
    the same block shapes as planner/audit.py's PINNED_CONFIGS,
    so a plan's top entry drops straight into ``deepspeed.initialize``."""
    if cand.disagg:
        n = (cand.disagg["prefill_replicas"]
             + cand.disagg["decode_replicas"])
        return {
            "train_micro_batch_size_per_gpu": 1,
            "serving": {"n_replicas": n,
                        "disagg": {"enabled": True, **cand.disagg}},
        }
    frag: Dict[str, Any] = {
        "train_micro_batch_size_per_gpu": cand.micro_batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "mesh": dict(cand.mesh),
        "zero_optimization": {"stage": cand.zero_stage},
    }
    off = cand.offload or {}
    if off.get("param"):
        frag["zero_optimization"]["offload_param"] = {
            "device": off["param"]}
    if off.get("optimizer"):
        block: Dict[str, Any] = {"device": off["optimizer"]}
        if off.get("chunked"):
            block["chunk_bytes"] = DEFAULT_CHUNK_BYTES
            block["working_set_bytes"] = DEFAULT_WORKING_SET_BYTES
        frag["zero_optimization"]["offload_optimizer"] = block
    if cand.comm_quantization:
        frag["comm_quantization"] = dict(cand.comm_quantization)
    if cand.step_schedule:
        frag["step_schedule"] = {"mode": "static",
                                 **copy.deepcopy(cand.step_schedule)}
    return frag


def validate_fragment(fragment: Dict[str, Any],
                      world_size: int = 1) -> None:
    """Round-trip the fragment through DeepSpeedConfig — a plan whose
    top entry does not parse is a planner bug, caught at emit time.
    Same code path a user's ``runtime.config.load_plan`` takes."""
    from deepspeed_tpu.runtime.config import load_plan

    load_plan(copy.deepcopy(fragment), world_size=world_size)


def compile_plan(model: ModelSpec, fleet: FleetSpec, *,
                 stages: Tuple[int, ...] = (0, 1, 2, 3),
                 gas: int = 1,
                 max_micro_batch: int = 64,
                 enable_quant: bool = True,
                 enable_offload: bool = True,
                 enable_schedule: bool = True,
                 serving: bool = False,
                 calibration: float = 1.0,
                 anchors: Optional[Dict[str, float]] = None,
                 top: Optional[int] = None,
                 validate_top: int = 3,
                 mesh_filter=None) -> Plan:
    """Enumerate → prune (predict_fit) → price (census × link class) →
    dedupe per placement key → rank by modeled throughput."""
    cands = enumerate_candidates(
        model, fleet, stages=stages, max_micro_batch=max_micro_batch,
        enable_quant=enable_quant, enable_offload=enable_offload,
        enable_schedule=enable_schedule, serving=serving,
        mesh_filter=mesh_filter)
    fit, pruned = prune_candidates(model, fleet, cands,
                                   calibration=calibration)
    best: Dict[Tuple, Tuple[Candidate, Dict[str, Any], Dict[str, Any],
                            Dict[str, Any]]] = {}
    for cand, fitres in fit:
        census = analytic_census(model, cand, gas=gas, fleet=fleet)
        if anchors:
            census = apply_anchors(census, anchors)
        timing = step_time(model, cand, fleet, gas=gas, census=census)
        key = cand.key()
        prev = best.get(key)
        if prev is None or (timing["tokens_per_sec_per_chip"]
                            > prev[3]["tokens_per_sec_per_chip"]):
            best[key] = (cand, fitres, census, timing)
    ordered = sorted(
        best.values(),
        key=lambda t: (-t[3]["tokens_per_sec_per_chip"], t[0].zero_stage,
                       _TIER_ORDER.get(t[0].offload_tier, 9),
                       -t[0].micro_batch))
    plan = Plan(model=model.name, seq_len=model.seq_len,
                fleet={"chips": fleet.chips, "hbm_bytes": fleet.hbm_bytes,
                       "host_bytes": fleet.host_bytes,
                       "nvme": fleet.nvme},
                gas=gas, pruned=pruned, n_candidates=len(cands))
    for i, (cand, fitres, census, timing) in enumerate(
            ordered[:top] if top else ordered, start=1):
        modes = {row["mode"] for row in census.values()}
        evidence = {
            "census": {k: {"count": r["count"],
                           "wire_bytes": r["wire_bytes"],
                           "link": r["link"], "mode": r["mode"]}
                       for k, r in sorted(census.items())},
            "census_mode": ("anchored" if modes == {"anchored"} else
                            "extrapolated" if modes in ({"extrapolated"},
                                                        set())
                            else "mixed"),
            "dominant_class": fitres["dominant_class"],
            "dominant_cost_term": timing["dominant_cost_term"],
            "overlap_fraction": round(timing["overlap_fraction"], 4),
            "predicted_peak_bytes": fitres["predicted_peak_bytes"],
            "predicted_step_ms": round(timing["step_seconds"] * 1e3, 3),
            "wire_bytes_total": timing["wire_bytes_total"],
        }
        assert tuple(sorted(evidence)) == tuple(sorted(PLAN_EVIDENCE_KEYS))
        plan.ranked.append(PlannedConfig(
            rank=i, candidate=cand.describe(),
            tokens_per_sec_per_chip=round(
                timing["tokens_per_sec_per_chip"], 3),
            config=config_fragment(model, cand, gas=gas),
            evidence=evidence))
    for entry in plan.ranked[:validate_top]:
        validate_fragment(entry.config, world_size=fleet.chips)
    return plan


# ---------------------------------------------------------------------
# regression-gate helpers: match a pinned bench-row config against a
# plan's ranking (mesh, stage, quant wire, offload tier — the dimensions
# a row pins; micro-batch/gas are workload knobs the gate ignores)
# ---------------------------------------------------------------------

def _frag_key(frag: Dict[str, Any], chips: int) -> Tuple:
    zero = frag.get("zero_optimization") or {}
    mesh = dict(frag.get("mesh") or {"data": chips})
    mesh = {k: int(v) for k, v in mesh.items() if int(v) > 1 or k == "data"}
    mesh.setdefault("data", 1)
    quant = (frag.get("comm_quantization") or {})
    wire = quant.get("grad_reduce") if quant.get("enabled") else None
    op = (zero.get("offload_param") or {}).get("device")
    oo = zero.get("offload_optimizer") or {}
    od = oo.get("device")
    chunked = bool(oo.get("working_set_bytes"))
    if op in (None, "none"):
        op = None
    if od in (None, "none"):
        od = None
    if op == "nvme":
        tier = "nvme"
    elif od == "nvme" and chunked:
        tier = "nvme_chunked"
    elif op == "cpu" and od == "cpu":
        tier = "cpu_chunked" if chunked else "cpu"
    elif od == "cpu":
        tier = "opt_cpu"
    else:
        tier = "none"
    return (tuple(sorted(mesh.items())), int(zero.get("stage", 0)),
            wire, tier)


def plan_rank_of(plan: Plan, known_good: Dict[str, Any],
                 chips: Optional[int] = None) -> Optional[int]:
    """1-based rank of the first planned entry whose placement matches
    the pinned fragment; None if the planner never proposed it."""
    n = chips or plan.fleet.get("chips") or 1
    want = _frag_key(known_good, n)
    for entry in plan.ranked:
        if _frag_key(entry.config, n) == want:
            return entry.rank
    return None


# ---------------------------------------------------------------------
# Autotuner seeding: ranked plan entries as tuning-space candidates
# ---------------------------------------------------------------------

def seed_candidates(model_cfg, *, seq_len: int, chips: int,
                    hbm_bytes: int, calibration: float = 1.0,
                    top: int = 8) -> List[Dict[str, Any]]:
    """The Autotuner's planner-mode space: top-N plan entries mapped to
    trial-candidate dicts ({zero_stage, micro_batch, mesh, overrides}),
    best first — trials then confirm the analytic ordering."""
    from deepspeed_tpu.planner.space import _moe_fraction
    from deepspeed_tpu.profiling import get_model_profile

    prof = get_model_profile(model_cfg, batch_size=1, seq_len=seq_len)
    spec = ModelSpec(name=getattr(model_cfg, "arch", "model"),
                     config=model_cfg, seq_len=seq_len,
                     num_params=int(prof["params"]),
                     moe_param_fraction=_moe_fraction(
                         model_cfg, int(prof["params"])))
    plan = compile_plan(spec, FleetSpec(chips=chips, hbm_bytes=hbm_bytes),
                        calibration=calibration, top=top, validate_top=0)
    out = []
    for entry in plan.ranked:
        frag = entry.config
        cand: Dict[str, Any] = {
            "zero_stage": frag["zero_optimization"]["stage"],
            "micro_batch": frag["train_micro_batch_size_per_gpu"],
            "mesh": dict(frag.get("mesh") or {"data": chips}),
            "est_bytes": entry.evidence["predicted_peak_bytes"],
        }
        overrides = {}
        for k in ("comm_quantization", "step_schedule"):
            if k in frag:
                overrides[k] = copy.deepcopy(frag[k])
        zo = {k: v for k, v in frag["zero_optimization"].items()
              if k != "stage"}
        if zo:
            overrides["zero_optimization"] = {
                "stage": frag["zero_optimization"]["stage"], **zo}
        if overrides:
            cand["overrides"] = overrides
        out.append(cand)
    return out


def save_plan(plan: Plan, path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(plan.to_dict(), f, indent=2, sort_keys=True)


def load_plan_file(path: str) -> Plan:
    with open(path, "r", encoding="utf-8") as f:
        return Plan.from_dict(json.load(f))
