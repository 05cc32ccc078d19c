"""Parallelism plan compiler (deepspeed_tpu/planner; docs/PLANNER.md).

Three families:

1. Regression gate — for every audit row with a pinned known-good
   config (planner.audit.PINNED_CONFIGS), the planner's row-mirroring
   query must rank that config in its TOP-3; the 6.7B chunked-offload ladder
   rung and a MoE expert-parallel placement must be proposed
   sight-unseen.
2. Cost-model properties — step time monotone in wire bytes at fixed
   overlap; overlap credit never exceeds the comm it hides; the
   anchored-vs-extrapolated census agrees within the frozen
   ANCHOR_TOLERANCE on a real lowered audit target.
3. Plumbing — fragment round-trip through runtime.config.load_plan,
   memory-model comm residual (error-feedback) pricing, Autotuner
   planner-mode seeding, and the CLI.
"""

import json

import pytest

from deepspeed_tpu.planner import (ANCHOR_TOLERANCE, PLAN_EVIDENCE_KEYS,
                                   Candidate, FleetSpec, ModelSpec, Plan,
                                   analytic_census, anchor_ratios,
                                   apply_anchors, compile_plan,
                                   plan_rank_of, seed_candidates,
                                   step_time)
from deepspeed_tpu.planner.audit import (PINNED_CONFIGS, PLAN_AUDIT_ROWS,
                                         plan_for_row)


# ---------------------------------------------------------------------
# 1. regression gate: known-good configs rank top-3
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def row_plans():
    return {name: plan_for_row(name) for name in PLAN_AUDIT_ROWS}


@pytest.mark.parametrize("name", PLAN_AUDIT_ROWS)
def test_known_good_ranks_top3(row_plans, name):
    plan = row_plans[name]
    rank = plan_rank_of(plan, PINNED_CONFIGS[name])
    assert rank is not None and rank <= 3, \
        (name, rank, [r.candidate for r in plan.ranked[:5]])


def test_ranked_entries_carry_frozen_evidence(row_plans):
    want = tuple(sorted(PLAN_EVIDENCE_KEYS))
    for name, plan in row_plans.items():
        assert plan.ranked, name
        for entry in plan.ranked:
            assert tuple(sorted(entry.evidence)) == want, name
            assert entry.evidence["predicted_peak_bytes"] > 0
            assert entry.evidence["predicted_step_ms"] > 0


@pytest.fixture(scope="module")
def plan_67b():
    model = ModelSpec.from_name("gpt2-6.7b", seq_len=512)
    fleet = FleetSpec(chips=1, hbm_bytes=16 << 30, host_bytes=64 << 30,
                      nvme=True)
    return compile_plan(model, fleet, max_micro_batch=4)


def test_67b_chunked_proposed_sight_unseen(plan_67b):
    """The peak_params acceptance rung: on a 1-chip 16GiB fleet with a
    64GiB host and NVMe, the planner must propose the chunked-offload
    config the r16 ladder pinned — without ever having run it."""
    rank = plan_rank_of(plan_67b, PINNED_CONFIGS["gpt2_6_7b_chunked"])
    assert rank is not None and rank <= 3, \
        (rank, [r.candidate for r in plan_67b.ranked])


def test_67b_losers_keep_pruning_reasons(plan_67b):
    """Device-resident tiers CANNOT hold 6.7B of optimizer state — the
    plan must say so with the dominant class and the shortfall."""
    assert plan_67b.pruned
    device_losers = [p for p in plan_67b.pruned
                     if "off:" not in p["candidate"]]
    assert device_losers
    for row in device_losers:
        assert row["reason"]
        assert row["dominant_class"]
        assert row["shortfall_bytes"] > 0
        assert row["predicted_peak_bytes"] > 16 << 30


def test_moe_expert_parallel_proposed_sight_unseen():
    """moe-1b-ep8 on 8 chips: an expert:8 placement must appear in the
    top-3 — the planner prices the all-to-all dispatch and the
    expert-sharded param win with no MoE bench row to copy from."""
    model = ModelSpec.from_name("moe-1b-ep8", seq_len=512)
    plan = compile_plan(model, FleetSpec(chips=8), max_micro_batch=8)
    assert plan.ranked
    top_meshes = [r.config.get("mesh") or {} for r in plan.ranked[:3]]
    assert any(m.get("expert") == 8 for m in top_meshes), top_meshes


# ---------------------------------------------------------------------
# 2. cost-model properties
# ---------------------------------------------------------------------

def _gpt2_350m_spec():
    return ModelSpec.from_name("gpt2-350m", seq_len=1024)


def test_step_time_monotone_in_wire_bytes():
    """At fixed overlap decisions, more bytes on the wire can never make
    the modeled step faster."""
    model = _gpt2_350m_spec()
    fleet = FleetSpec(chips=8)
    cand = Candidate(mesh={"data": 8}, zero_stage=2, micro_batch=4)
    census = analytic_census(model, cand, gas=2, fleet=fleet)
    assert census, "expected DP collectives in the census"
    prev = None
    for scale in (0.5, 1.0, 2.0, 8.0, 64.0):
        scaled = {k: {**r, "wire_bytes": int(r["wire_bytes"] * scale)}
                  for k, r in census.items()}
        t = step_time(model, cand, fleet, gas=2, census=scaled)
        if prev is not None:
            assert t["step_seconds"] >= prev - 1e-12, scale
        prev = t["step_seconds"]


def test_overlap_credit_never_exceeds_comm():
    """The credit hides comm behind compute — it can never exceed the
    comm there is, nor drive exposed comm negative."""
    model = _gpt2_350m_spec()
    fleet = FleetSpec(chips=8)
    for cand in (
        Candidate(mesh={"data": 8}, zero_stage=1, micro_batch=2,
                  step_schedule={"weight_update": "decomposed",
                                 "fused_reduce_scatter": True}),
        Candidate(mesh={"data": 8}, zero_stage=3, micro_batch=2,
                  step_schedule={"gather_prefetch_depth": 2,
                                 "fused_gather_matmul": True}),
        Candidate(mesh={"data": 2, "seq": 4}, zero_stage=2, micro_batch=2,
                  step_schedule={"ring_interleave": 2}),
    ):
        census = analytic_census(model, cand, gas=1, fleet=fleet)
        t = step_time(model, cand, fleet, gas=1, census=census)
        assert t["overlap_credit_seconds"] <= t["comm_seconds"] + 1e-12
        assert t["exposed_comm_seconds"] >= -1e-12
        assert t["exposed_comm_seconds"] + t["overlap_credit_seconds"] \
            == pytest.approx(t["comm_seconds"])


def test_anchored_census_within_frozen_tolerance():
    """Anchor/extrapolate protocol: the analytic census of the
    train_zero1 audit target's exact shape must agree with the REAL
    lowered census within ANCHOR_TOLERANCE (docs/PLANNER.md)."""
    from deepspeed_tpu.analysis.targets import run_target_audits
    from deepspeed_tpu.models import get_model_config

    rep, _ = run_target_audits("train_zero1", memory=False)
    measured = rep.census_summary()
    cfg = get_model_config("gpt2-tiny", max_seq_len=64)
    model = ModelSpec.from_name("gpt2-tiny", seq_len=64, max_seq_len=64)
    assert model.config.hidden_size == cfg.hidden_size
    cand = Candidate(mesh={"data": 8}, zero_stage=1, micro_batch=1)
    ratios = anchor_ratios(measured, model, cand, gas=2)
    assert "all-reduce" in ratios, (measured.keys(), ratios)
    for kind, ratio in ratios.items():
        assert 1.0 / ANCHOR_TOLERANCE <= ratio <= ANCHOR_TOLERANCE, \
            (kind, ratio)
    # anchored rows are marked, un-anchored rows stay extrapolated
    census = analytic_census(model, cand, gas=2)
    anchored = apply_anchors(census, ratios)
    assert anchored["all-reduce"]["mode"] == "anchored"


def test_anchors_flow_into_plan_evidence():
    model = _gpt2_350m_spec()
    plan = compile_plan(model, FleetSpec(chips=8), stages=(1,),
                        enable_quant=False, enable_offload=False,
                        max_micro_batch=4, anchors={"all-reduce": 1.5})
    assert plan.ranked
    top = plan.ranked[0].evidence
    assert top["census_mode"] in ("anchored", "mixed")
    assert top["census"]["all-reduce"]["mode"] == "anchored"


# ---------------------------------------------------------------------
# 3a. memory model: comm-quantization error-feedback residual
# ---------------------------------------------------------------------

def test_memory_breakdown_has_comm_class():
    from deepspeed_tpu.autotuning.autotuner import (ModelInfo,
                                                    estimate_memory_breakdown)

    info = ModelInfo(num_params=100_000_000, hidden_size=1024,
                     num_layers=24, vocab_size=50257)
    base = estimate_memory_breakdown(info, zero_stage=1, dp_size=8,
                                     micro_batch=1, seq_len=1024)
    quant = estimate_memory_breakdown(info, zero_stage=1, dp_size=8,
                                      micro_batch=1, seq_len=1024,
                                      comm_quant=True)
    assert base["comm"] == 0
    # fp32 EF residual: one padded row per device ≈ 4 B/param
    assert quant["comm"] >= 4 * info.num_params
    # not eligible: stage 3 regathers, nothing replicated to feed back
    z3 = estimate_memory_breakdown(info, zero_stage=3, dp_size=8,
                                   micro_batch=1, seq_len=1024,
                                   comm_quant=True)
    assert z3["comm"] == 0


def test_comm_residual_flips_fit_verdict():
    """The regression the satellite fixes: a quantized-DP config whose
    EF residual is the difference between fitting and OOM must now be
    rejected by predict_fit."""
    from deepspeed_tpu.autotuning.autotuner import ModelInfo, predict_fit

    info = ModelInfo(num_params=400_000_000, hidden_size=1024,
                     num_layers=24, vocab_size=50257)
    kwargs = dict(zero_stage=1, dp_size=8, micro_batch=1, seq_len=1024)
    base = predict_fit(info, hbm_bytes=1 << 62, **kwargs)
    # budget: just above the un-quantized peak, well below peak + 4B/p
    budget = base["predicted_peak_bytes"] + (1 << 20)
    assert predict_fit(info, hbm_bytes=budget, **kwargs)["predicted_fit"]
    quant = predict_fit(info, hbm_bytes=budget, comm_quant=True, **kwargs)
    assert not quant["predicted_fit"]
    assert quant["dominant_class"] == "comm"


# ---------------------------------------------------------------------
# 3b. plan round-trip + seeding + CLI
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_plan():
    model = ModelSpec.from_name("gpt2-350m", seq_len=1024)
    return compile_plan(model, FleetSpec(chips=8), enable_quant=False,
                        max_micro_batch=8, top=5)


def test_plan_roundtrip_through_load_plan(tmp_path, small_plan):
    from deepspeed_tpu.planner import save_plan
    from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                              load_plan)

    path = str(tmp_path / "plan.json")
    save_plan(small_plan, path)
    cfg = load_plan(path, world_size=8)
    assert cfg.train_micro_batch_size_per_gpu == \
        small_plan.ranked[0].config["train_micro_batch_size_per_gpu"]
    # rank selection + bare-fragment mode + failure mode
    cfg2 = load_plan(small_plan.ranked[1].config, world_size=8)
    assert cfg2.zero_config.stage == \
        small_plan.ranked[1].config["zero_optimization"]["stage"]
    with pytest.raises(DeepSpeedConfigError):
        load_plan(path, world_size=8, rank=99)
    # Plan serialization round-trips losslessly
    again = Plan.from_dict(json.loads(json.dumps(small_plan.to_dict())))
    assert again.to_dict() == small_plan.to_dict()


def test_seed_candidates_feed_autotuner_space():
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.models import get_model_config

    cfg = get_model_config("gpt2-tiny", max_seq_len=64)
    cands = seed_candidates(cfg, seq_len=64, chips=8,
                            hbm_bytes=16 << 30, top=4)
    assert cands
    for c in cands:
        assert set(c) >= {"zero_stage", "micro_batch", "mesh",
                          "est_bytes"}
    tuner = Autotuner(cfg, {"optimizer": {"type": "AdamW",
                                          "params": {"lr": 1e-4}}},
                      seq_len=64, mode="planner", max_trials=4,
                      n_devices=8)
    space = tuner._space()
    assert 0 < len(space) <= 4
    # the trial config applies the candidate's override blocks
    trial = tuner._trial_config(space[0])
    assert trial["zero_optimization"]["stage"] == space[0]["zero_stage"]


def test_cli_writes_valid_plan_json(tmp_path, capsys):
    from deepspeed_tpu.planner.cli import main

    out = str(tmp_path / "plan.json")
    rc = main(["--model", "gpt2-350m", "--chips", "8", "--top", "3",
               "--no-quant", "--max-micro-batch", "4",
               "--calibration", "none", "--json", out])
    assert rc == 0
    text = capsys.readouterr().out
    assert "tok/s/chip" in text
    data = json.load(open(out))
    assert data["ranked"]
    from deepspeed_tpu.runtime.config import load_plan
    load_plan(out, world_size=8)


def test_cli_no_fit_exits_nonzero(tmp_path):
    from deepspeed_tpu.planner.cli import main

    # 6.7B on one 16GiB chip with no host and no NVMe: nothing fits
    rc = main(["--model", "gpt2-6.7b", "--chips", "1", "--seq", "512",
               "--no-offload", "--calibration", "none"])
    assert rc == 1


# ---------------------------------------------------------------------
# 3c. a plain config fragment is what the planner reads
# ---------------------------------------------------------------------

def test_config_fragment_reads_as_chunked_nvme(plan_67b):
    """A resolved config block (paths and batch keys included) is
    fragment-shaped: the planner reads its tier from it directly."""
    from deepspeed_tpu.planner.rank import _frag_key

    frag = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 2,
        "mesh": {"data": 1},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "cpu"},
            "offload_optimizer": {"device": "nvme", "nvme_path": "/x",
                                  "working_set_bytes": 1 << 30,
                                  "chunk_bytes": 64 << 20}},
    }
    assert _frag_key(frag, 1) == ((("data", 1),), 3, None, "nvme_chunked")
    rank = plan_rank_of(plan_67b, frag)
    assert rank is not None
    assert rank == plan_rank_of(plan_67b,
                                PINNED_CONFIGS["gpt2_6_7b_chunked"])
    # without a working set the same block is no chunked tier
    del frag["zero_optimization"]["offload_optimizer"]["working_set_bytes"]
    assert _frag_key(frag, 1)[3] != "nvme_chunked"


def test_pinned_configs_cover_audit_rows():
    assert set(PINNED_CONFIGS) >= set(PLAN_AUDIT_ROWS)
