"""Plan-compiler search space: fleet/model specs + candidate enumeration.

The offline half of the reference autotuner (PAPER.md layer 8): instead
of *running* candidate configs, enumerate the whole (mesh × ZeRO stage ×
comm_quantization × step_schedule fusion × offload tier × disagg split)
space symbolically (Placement Semantics, arXiv:2601.02311) and let the
calibrated memory model (``predict_fit``) prune what cannot fit before
anything is priced.  Survivors go to :mod:`deepspeed_tpu.planner.cost`.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.autotuning.autotuner import (ModelInfo, enumerate_meshes,
                                                predict_fit)

# offload tiers, from everything on the device to everything on NVMe:
# device-resident → host optimizer → full host → chunked
# host pipeline (PR 16) → NVMe chunk files → full NVMe
OFFLOAD_TIERS: Tuple[Tuple[str, Optional[Dict[str, Any]]], ...] = (
    ("none", None),
    ("opt_cpu", {"param": None, "optimizer": "cpu", "chunked": False}),
    ("cpu", {"param": "cpu", "optimizer": "cpu", "chunked": False}),
    ("cpu_chunked", {"param": "cpu", "optimizer": "cpu", "chunked": True}),
    ("nvme_chunked", {"param": "cpu", "optimizer": "nvme", "chunked": True}),
    ("nvme", {"param": "nvme", "optimizer": "nvme", "chunked": False}),
)

DEFAULT_CHUNK_BYTES = 64 << 20
DEFAULT_WORKING_SET_BYTES = 1 << 30


@dataclass(frozen=True)
class FleetSpec:
    """What the hardware offers: chips, per-chip HBM, host RAM behind
    them, NVMe, and the link classes (bytes/s) the cost model prices
    wire traffic against (docs/PLANNER.md "Link classes")."""
    chips: int = 8
    hbm_bytes: int = 16 << 30
    host_bytes: Optional[int] = None
    nvme: bool = False
    ici_bytes_per_s: float = 9.0e10     # intra-slice interconnect
    dcn_bytes_per_s: float = 6.25e9     # inter-slice data-center network
    pcie_bytes_per_s: float = 1.6e10    # host <-> device
    nvme_bytes_per_s: float = 3.0e9     # NVMe streaming
    peak_flops: float = 1.97e14         # bf16 per chip
    dcn_axes: Tuple[str, ...] = ()      # mesh axes that cross DCN

    def link_speed(self, link: str) -> float:
        return {"ici": self.ici_bytes_per_s, "dcn": self.dcn_bytes_per_s,
                "pcie": self.pcie_bytes_per_s,
                "nvme": self.nvme_bytes_per_s}[link]


@dataclass
class ModelSpec:
    """What is being trained/served: a registry TransformerConfig plus
    the workload sequence length, with the analytic param count (and the
    expert-parallel-shardable fraction of it) precomputed."""
    name: str
    config: Any
    seq_len: int
    num_params: int = 0
    moe_param_fraction: float = 0.0

    @classmethod
    def from_name(cls, name: str, seq_len: Optional[int] = None,
                  **overrides) -> "ModelSpec":
        from deepspeed_tpu.models.registry import get_model_config
        from deepspeed_tpu.profiling import get_model_profile

        cfg = get_model_config(name, **overrides)
        s = int(seq_len or cfg.max_seq_len)
        prof = get_model_profile(cfg, batch_size=1, seq_len=s)
        return cls(name=name, config=cfg, seq_len=s,
                   num_params=int(prof["params"]),
                   moe_param_fraction=_moe_fraction(cfg, prof["params"]))

    def model_info(self) -> ModelInfo:
        return ModelInfo(num_params=self.num_params,
                         hidden_size=self.config.hidden_size,
                         num_layers=self.config.num_layers,
                         vocab_size=self.config.vocab_size)


def _moe_fraction(cfg, total_params: int) -> float:
    if not getattr(cfg, "num_experts", 0):
        return 0.0
    n_mats = 3 if getattr(cfg, "activation", "") == "swiglu" else 2
    ffn = getattr(cfg, "moe_intermediate_size", None) or cfg.intermediate_size
    freq = max(1, getattr(cfg, "moe_layer_freq", 1) or 1)
    moe_layers = -(-cfg.num_layers // freq)
    expert_p = moe_layers * cfg.num_experts * n_mats * cfg.hidden_size * ffn
    return min(1.0, expert_p / max(1, total_params))


@dataclass
class Candidate:
    """One point of the config space.  ``key()`` collapses the
    micro-batch and schedule sweep: ranking keeps the best variant per
    (mesh, stage, quant wire, offload tier) so the top-N list shows
    *distinct* placements, not one placement's batch ladder."""
    mesh: Dict[str, int]
    zero_stage: int
    micro_batch: int
    comm_quantization: Optional[Dict[str, Any]] = None
    step_schedule: Optional[Dict[str, Any]] = None
    offload: Optional[Dict[str, Any]] = None
    offload_tier: str = "none"
    disagg: Optional[Dict[str, int]] = None

    def axis(self, name: str) -> int:
        return int(self.mesh.get(name, 1) or 1)

    @property
    def dp_size(self) -> int:
        return self.axis("data") * self.axis("expert")

    def key(self) -> Tuple:
        return (tuple(sorted(self.mesh.items())), self.zero_stage,
                (self.comm_quantization or {}).get("grad_reduce"),
                self.offload_tier,
                tuple(sorted((self.disagg or {}).items())))

    def describe(self) -> str:
        bits = ["x".join(f"{k}{v}" for k, v in sorted(self.mesh.items())),
                f"zero{self.zero_stage}", f"mb{self.micro_batch}"]
        if self.comm_quantization:
            bits.append(f"q:{self.comm_quantization.get('grad_reduce')}")
        if self.offload_tier != "none":
            bits.append(f"off:{self.offload_tier}")
        if self.step_schedule:
            bits.append("sched")
        if self.disagg:
            bits.append(f"disagg:{self.disagg['prefill_replicas']}p"
                        f"{self.disagg['decode_replicas']}d")
        return " ".join(bits)


def schedule_for(mesh: Dict[str, int], zero_stage: int) -> Optional[Dict[str, Any]]:
    """The deterministic pinned-fusion block the overlap scheduler's
    decide() table would land on for this shape (overlap_scheduler.py):
    ZeRO-3 → prefetch + fused gather; ring sequence → interleave 2;
    replicated-grad DP → decomposed update + fused reduce-scatter."""
    d = mesh.get("data", 1) * mesh.get("expert", 1)
    if zero_stage >= 3 and d > 1:
        return {"gather_prefetch_depth": 2,
                "param_persistence_threshold": 100_000,
                "prefetch_bucket_size": 50_000_000,
                "fused_gather_matmul": True}
    if mesh.get("seq", 1) > 1:
        return {"ring_interleave": 2}
    if zero_stage <= 1 and d > 1:
        return {"weight_update": "decomposed", "fused_reduce_scatter": True}
    return None


def _quant_eligible(mesh: Dict[str, int], zero_stage: int) -> bool:
    # mirrors the engine's quantized-DP gate: dp > 1, pure data mesh,
    # stage <= 2 (engine.py warn-fallback conditions)
    return (zero_stage <= 2 and mesh.get("data", 1) > 1
            and set(mesh) <= {"data"})


def enumerate_candidates(model: ModelSpec, fleet: FleetSpec, *,
                         stages: Tuple[int, ...] = (0, 1, 2, 3),
                         max_micro_batch: int = 64,
                         enable_quant: bool = True,
                         enable_offload: bool = True,
                         enable_schedule: bool = True,
                         serving: bool = False,
                         mesh_filter=None) -> List[Candidate]:
    """The full candidate lattice BEFORE memory pruning.
    ``mesh_filter(mesh) -> bool`` restricts the mesh sweep — how a
    row-mirroring query pins its experiment's placement family (e.g.
    the longseq_ring row shards the sequence over EVERY chip)."""
    if serving:
        return _serving_candidates(model, fleet)
    out: List[Candidate] = []
    ring = getattr(model.config, "seq_impl", "") == "ring"
    for mesh in enumerate_meshes(fleet.chips, model.config):
        if mesh_filter is not None and not mesh_filter(mesh):
            continue
        sp = mesh.get("seq", 1)
        if sp > 1 and model.seq_len % sp:
            continue
        if ring and sp <= 1 and fleet.chips > 1:
            continue  # ring attention demands a sequence axis
        pure_data = set(mesh) <= {"data"}
        for stage in stages:
            if mesh.get("pipe", 1) > 1 and stage >= 2:
                continue  # pipeline composes with ZeRO-0/1 only
            quants: List[Optional[Dict[str, Any]]] = [None]
            if enable_quant and _quant_eligible(mesh, stage):
                quants.append({"enabled": True, "grad_reduce": "int8"})
            tiers = [OFFLOAD_TIERS[0]]
            if enable_offload and pure_data:
                for name, tier in OFFLOAD_TIERS[1:]:
                    if tier["param"] and stage != 3:
                        continue  # param offload is a ZeRO-3 feature
                    if tier["optimizer"] and stage < 1:
                        continue  # offloaded masters need sharded masters
                    if ("nvme" in (tier["param"], tier["optimizer"])
                            and not fleet.nvme):
                        continue
                    tiers.append((name, tier))
            mb = 1
            while mb <= max_micro_batch:
                for quant, (tier_name, tier) in itertools.product(
                        quants, tiers):
                    if quant and tier:
                        continue  # engine gate: quantized DP is
                        # incompatible with the offloaded optimizer store
                    scheds: List[Optional[Dict[str, Any]]] = [None]
                    if enable_schedule:
                        s = schedule_for(mesh, stage)
                        if s:
                            scheds.append(s)
                    for sched in scheds:
                        out.append(Candidate(
                            mesh=dict(mesh), zero_stage=stage,
                            micro_batch=mb,
                            comm_quantization=dict(quant) if quant else None,
                            step_schedule=dict(sched) if sched else None,
                            offload=dict(tier) if tier else None,
                            offload_tier=tier_name))
                mb *= 2
    return out


def _serving_candidates(model: ModelSpec, fleet: FleetSpec) -> List[Candidate]:
    """Disaggregated serving splits: partition the fleet's replicas into
    prefill/decode tiers (serving/disagg.py semantics; one chip per
    replica here — the per-replica mesh sweep stays a training concern)."""
    out = []
    n = fleet.chips
    for p in range(1, n):
        out.append(Candidate(
            mesh={"data": 1}, zero_stage=0, micro_batch=1,
            disagg={"prefill_replicas": p, "decode_replicas": n - p}))
    return out


def prune_candidates(model: ModelSpec, fleet: FleetSpec,
                     candidates: List[Candidate], *,
                     calibration: float = 1.0
                     ) -> Tuple[List[Tuple[Candidate, Dict[str, Any]]],
                                List[Dict[str, Any]]]:
    """predict_fit gate over the lattice → (survivors with their fit
    record, pruned losers with machine-readable reasons).  Host-RAM and
    O(chunk) working-set pricing ride along via predict_fit's offload
    re-homing (ZeRO-Offload, arXiv:2101.06840)."""
    mi = model.model_info()
    fit: List[Tuple[Candidate, Dict[str, Any]]] = []
    pruned: List[Dict[str, Any]] = []
    for cand in candidates:
        if cand.disagg:
            # serving: weights + one sequence of KV per replica chip —
            # no grads/optimizer classes exist at inference time
            c = model.config
            kv = (c.num_layers * 2 * model.seq_len
                  * c.kv_heads * c.dim_per_head * 2)
            need = int((model.num_params * 2 + kv) * calibration)
            if need <= fleet.hbm_bytes:
                fit.append((cand, {"predicted_peak_bytes": need,
                                   "predicted_fit": True,
                                   "dominant_class": "params",
                                   "breakdown": {"params": model.num_params * 2,
                                                 "kv_cache": kv},
                                   "shortfall_bytes": 0}))
            else:
                pruned.append({"candidate": cand.describe(),
                               "reason": (f"device_oom: params class, "
                                          f"{need - fleet.hbm_bytes} bytes "
                                          f"over {fleet.hbm_bytes} budget"),
                               "dominant_class": "params",
                               "shortfall_bytes": need - fleet.hbm_bytes,
                               "predicted_peak_bytes": need})
            continue
        off = cand.offload or {}
        res = predict_fit(
            mi, cand.zero_stage, max(1, cand.dp_size), cand.micro_batch,
            model.seq_len, hbm_bytes=fleet.hbm_bytes,
            calibration=calibration,
            tp_size=cand.axis("tensor"), pp_size=cand.axis("pipe"),
            sp_size=cand.axis("seq"),
            offload_param=off.get("param"),
            offload_optimizer=off.get("optimizer"),
            host_bytes=fleet.host_bytes,
            chunk_bytes=DEFAULT_CHUNK_BYTES if off.get("chunked") else None,
            comm_quant=bool(cand.comm_quantization))
        if res["predicted_fit"]:
            fit.append((cand, res))
        else:
            budget = (fleet.hbm_bytes
                      if res["predicted_peak_bytes"] > fleet.hbm_bytes
                      else fleet.host_bytes)
            where = ("device" if res["predicted_peak_bytes"]
                     > fleet.hbm_bytes else "host")
            pruned.append({
                "candidate": cand.describe(),
                "reason": (f"{where}_oom: {res['dominant_class']} class, "
                           f"{res['shortfall_bytes']} bytes over "
                           f"{budget} budget"),
                "dominant_class": res["dominant_class"],
                "shortfall_bytes": res["shortfall_bytes"],
                "predicted_peak_bytes": res["predicted_peak_bytes"],
            })
    return fit, pruned
