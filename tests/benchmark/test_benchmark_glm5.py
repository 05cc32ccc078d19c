"""The ``glm-5-ep16-l5`` configuration and the self-drafting step's
per-layer metrics: the file holds the catalog row's config but for its
four cuts and builds the model it describes, the cell's traffic as the
issue names it, the two readers on a synthetic run and on another
program's, the cell at the tiny preset end to end in a temporary copy
(both new metrics on the traced line), and ``tools/gate_probe_mtp.py`` at
the tiny preset."""

import json
import time
from pathlib import Path

import jax
import pytest

import bench_tiny
from benchmark.lib import device, harness, manifest
from benchmark.lib.model import build_model
from benchmark.lib.run import Run

ROOT = Path(__file__).resolve().parents[2]
CELL = "glm-5-ep16-l5.reason_open"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUTS = ["num_hidden_layers", "n_routed_experts", "vocab_size",
        "first_k_dense_replace"]
TINY_CELL = "glm-5-tiny.tiny_open"

# the published names the reference reads, at the registry's tiny sizes:
# index_topk 8, far below the contexts
TINY = {
    "source": "tests", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "first_k_dense_replace": 2, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_parameters": {"rope_theta": 1e4, "rope_type": "default"},
    "rope_interleave": True, "indexer_rope_interleave": True,
    "index_topk": 8, "index_n_heads": 4, "index_head_dim": 16,
    "num_experts_per_tok": 4, "n_routed_experts": 4,
    "experts_held_first": 4, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "num_nextn_predict_layers": 1,
    "reduced": [], "assumed": {}, "kind": "serve",
    "reference": "glm_moe_dsa",
    "registry": {"name": "glm-5-tiny",
                 "overrides": {"param_dtype": "bfloat16"}},
    "expect": {"num_layers": 4, "index_topk": 8, "experts_held": 4,
               "n_routed_experts": 16, "latent_row": 32, "window_row": 0,
               "mtp_layers": 1, "first_k_dense": 2,
               "routed_scaling_factor": 2.5},
    # float32 arithmetic on the bf16 weights (as the tiny dots3 cell:
    # with 8 keys chosen of a hundred bf16 noise swaps choices)
    "engine_config": {
        "dtype": "float32", "self_draft": True,
        "memory_config": {"num_blocks": 128, "block_size": 16},
        "max_context": 128,
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 32}},
    "server_config": {}, "logit_rms_tolerance": 0.05}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``bench_tiny``'s copy, and on top of it the tiny GLM-5 preset under
    the open-loop mix, added the way this PR adds the real one: a file, an
    entry each, and the cell's name at the end of the lists the real cell
    is on."""
    dst = bench_tiny.make_copy(tmp_path_factory.mktemp("bench_glm5"))
    (dst / "benchmark/configs/glm-5-tiny.json").write_text(json.dumps(TINY))
    man = json.loads((dst / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "glm-5-tiny", "source": "tests",
                           "file": "benchmark/configs/glm-5-tiny.json",
                           "reduced": [], "why": "tiny preset"})
    man["workloads"].append({"name": TINY_CELL, "config": "glm-5-tiny",
                             "traffic": "tiny_open", "chips": 1,
                             "why": "tiny preset"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY_CELL)
    (dst / "BENCHMARK.json").write_text(json.dumps(man))
    return dst


@pytest.fixture
def plain_jit(monkeypatch):
    """No persistent cache, and the served steps compiled under the same
    matmul precision as the warm-up (the serve loop's thread would not
    see a ``with``)."""
    monkeypatch.setattr(device, "setup_compile_cache", lambda: "(off)")
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", was)


def _config():
    return json.loads((ROOT / "benchmark/configs/glm-5-ep16-l5.json")
                      .read_text())


def test_the_file_builds_the_model_it_describes():
    cfg = _config()
    model = build_model(cfg)
    m, w = model.mla, model.mla.full
    assert cfg["reduced"] == CUTS
    assert cfg["published"] == {"num_hidden_layers": 78,
                                "n_routed_experts": 256,
                                "vocab_size": 154880,
                                "first_k_dense_replace": 3}
    assert (model.num_layers, model.vocab_size, m.experts_held,
            m.first_k_dense, m.mtp_layers) == (
        cfg["num_hidden_layers"], cfg["vocab_size"],
        (cfg["experts_held_first"], cfg["n_routed_experts"]),
        cfg["first_k_dense_replace"], cfg["num_nextn_predict_layers"])
    assert (w.num_heads, w.q_lora_rank, w.kv_lora_rank, w.qk_nope_head_dim,
            w.qk_rope_head_dim, w.v_head_dim, w.qk_head_dim,
            w.rope_theta) == tuple(
        cfg[k] for k in ("num_attention_heads", "q_lora_rank",
                         "kv_lora_rank", "qk_nope_head_dim",
                         "qk_rope_head_dim", "v_head_dim", "qk_head_dim")
    ) + (cfg["rope_parameters"]["rope_theta"],)
    assert (m.index_topk, m.index_heads, m.index_head_dim,
            m.n_routed_experts, m.num_experts_per_tok,
            m.moe_intermediate_size, m.routed_scaling_factor,
            m.rope_interleaved, m.n_shared_experts) == (
        cfg["index_topk"], cfg["index_n_heads"], cfg["index_head_dim"],
        cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"], cfg["routed_scaling_factor"],
        cfg["rope_interleave"] and cfg["indexer_rope_interleave"],
        cfg["n_shared_experts"])
    assert (m.gate, m.lora_rescale, m.window,
            m.has_window(model.num_layers)) == (False, False, None, False)
    assert (cfg["n_group"], cfg["topk_group"]) == (1, 1)
    assert (model.hidden_size, model.intermediate_size,
            model.layernorm_eps, model.max_seq_len) == (
        cfg["hidden_size"], cfg["intermediate_size"], cfg["rms_norm_eps"],
        cfg["max_position_embeddings"])
    assert set(cfg["assumed"]) >= {
        "indexer", "rope_layout", "attention", "routing",
        "e_score_correction_bias", "mtp", "self_drafting", "initialisation"}
    # the engine: self-drafting on from the file, and what a token holds
    eng = cfg["engine_config"]
    mem, state = eng["memory_config"], eng["state_manager"]
    assert eng["self_draft"] is True and cfg["server_config"] == {}
    rows = mem["num_blocks"] * mem["block_size"]
    assert m.cache_layers(model.num_layers) == 6
    assert rows * 6 * (640 + 128) * 2 == 1152 * 2 ** 20       # the pages
    assert eng["max_context"] // mem["block_size"] == 64
    assert state["min_context_blocks"] * mem["block_size"] == 1024


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_config_but_for_its_cuts():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "GLM-5")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "-") != v]
    assert sorted(differs) == sorted(CUTS) == sorted(cfg["reduced"])
    entry = next(c for c in manifest.load_manifest(ROOT)["configs"]
                 if c["name"] == "glm-5-ep16-l5")
    assert entry["reduced"] == CUTS and entry["source"] == cfg["source"]


def test_the_cells_traffic_is_what_the_issue_names():
    cell = manifest.load_cell(ROOT, CELL)
    t = cell.traffic
    assert cell.chips == 1 and t["driver"] == "open_loop"
    assert t["prompt_tokens"] == {"min": 1024, "max": 4096}
    assert t["answer_tokens"]["min"] == 512
    assert t["answer_tokens"]["max"] in (2048, 1024)
    assert (t["answer_follows_prompt"], t["block"], t["drain_s"]) == (
        False, 8, 60)
    others = {json.loads(p.read_text()).get("base_seed")
              for p in (ROOT / "benchmark/traffic").glob("*.json")
              if p.stem != "reason_open"}
    assert t["base_seed"] not in others
    assert 4096 + t["answer_tokens"]["max"] <= cell.config[
        "engine_config"]["max_context"]
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_mean_ms", "token_gap_mean_ms", "setup_s"}
    assert set(cell.readers) == {
        "queue_wait_p50_ms.open", "serve_step_ms_p50.open",
        "prefill_tokens_per_s.open", "compiles_in_window.open",
        "pallas_time_share.open", "device_idle_share.open",
        "loadgen_late_p95_ms", "ttft_p95_ms.open", "token_gap_p95_ms.open",
        "selected_share_p50", "draft_accept_share", "verify_runs_p50"}
    by = {m["name"]: m for m in cell.per_layer}
    for name in ("draft_accept_share", "verify_runs_p50"):
        assert (by[name]["layer"], by[name]["moves"], by[name]["better"],
                by[name]["workloads"]) == ("ragged step",
                                           "token_gap_mean_ms", "higher",
                                           [CELL])


def _synthetic_run():
    lo, hi = 1_000_000.0, 21_000_000.0
    spans = []
    for i in range(9):
        ts = lo + 1e6 + i * 1e5
        spans.append({"ph": "X", "name": "v2.schedule", "ts": ts,
                      "dur": 50.0, "args": {"seqs": 12, "tokens": 24,
                                            "verify_runs": 4 + i,
                                            "draft_rows": 4 + i,
                                            "mtp_rows": 24}})
        spans.append({"ph": "X", "name": "v2.fetch", "ts": ts + 500,
                      "dur": 900.0, "args": {"drafts": 4 + i,
                                             "accepted": 3 + (i % 2)}})
    # outside the window: not counted
    spans.append({"ph": "X", "name": "v2.fetch", "ts": hi + 5.0, "dur": 1.0,
                  "args": {"drafts": 1000, "accepted": 0}})
    spans.append({"ph": "X", "name": "v2.schedule", "ts": lo + 5.0,
                  "dur": 1.0, "args": {"seqs": 0, "tokens": 0}})
    return Run(correct=True, attempted=1, failed=0, end_to_end={},
               setup_s=1.0, spans=spans,
               counters={"window_mono_us": (lo, hi)})


def test_the_two_readers_on_a_synthetic_run_and_on_another_program():
    cell = manifest.load_cell(ROOT, CELL)
    run = _synthetic_run()
    drafts = sum(4 + i for i in range(9))
    accepted = sum(3 + (i % 2) for i in range(9))
    assert cell.readers["draft_accept_share"](run, cell) \
        == pytest.approx(accepted / drafts)
    assert cell.readers["verify_runs_p50"](run, cell) == 8
    # an engine that does not draft for itself (and the parent commit's):
    # no such argument in a span, and nothing is raised
    for e in run.spans:
        e["args"] = {"seqs": 1, "tokens": 3} \
            if e["name"] == "v2.schedule" else {}
    for name in ("draft_accept_share", "verify_runs_p50"):
        assert cell.readers[name](run, cell) is None
    run.spans = []
    for name in ("draft_accept_share", "verify_runs_p50"):
        assert cell.readers[name](run, cell) is None


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_a_tiny_self_drafting_cell_end_to_end(copy, traced, plain_jit):
    out = harness.run_cell(copy, TINY_CELL, 2 ** 31 + 5, 1.5, traced,
                           time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    if not traced:
        assert set(out["metrics"]) == {"ttft_mean_ms", "token_gap_mean_ms",
                                       "setup_s"}
        return
    # no device plane here, so the readers of the trace find nothing and
    # leave their metrics out; the program's spans are read on the CPU too
    assert set(out["metrics"]) == {
        "queue_wait_p50_ms.open", "serve_step_ms_p50.open",
        "prefill_tokens_per_s.open", "compiles_in_window.open",
        "ttft_p95_ms.open", "token_gap_p95_ms.open", "loadgen_late_p95_ms",
        "selected_share_p50", "draft_accept_share", "verify_runs_p50",
        "steps_counted"}
    assert 0 < out["metrics"]["draft_accept_share"]["value"] < 1
    assert out["metrics"]["verify_runs_p50"]["value"] >= 1
    # the warm-up's steps ran the programs the serve loop used
    assert out["metrics"]["compiles_in_window.open"]["value"] == 0


def test_gate_probe_mtp_at_the_tiny_preset(copy, capsys):
    """The probe on the tiny cell of a temporary copy, float32: verify
    rows and module rows read as the gate does and the self-drafted
    stream is the plain one.  (No leaf of the tiny preset is large enough
    for the probe to send it through int8: that variant reads the same
    here, and is the chip's to show.)"""
    probe = manifest.load_code(copy, "tools", "gate_probe_mtp")
    assert probe.main([TINY_CELL, "5", "short=40", "long=90", "decode=20",
                       "live=4", "answer=24"],
                      root=copy, need_chip=False) == 0
    lines = capsys.readouterr().out.splitlines()
    load, = [json.loads(x[5:]) for x in lines if x.startswith("LOAD ")]
    assert load["positions"] == 4 * 24 and load["streams_agree_share"] == 1.0
    assert load["h2d_arrays"] == [1] and load["dispatch_programs"] == [1]
    assert load["fetches_per_step"] == 1.0
    assert load["steps_with_verify_runs_and_chunks"] > 0
    assert 0 < load["accept_share"] < 1
    gate = [json.loads(x[5:]) for x in lines if x.startswith("GATE ")]
    mtp = [json.loads(x[4:]) for x in lines if x.startswith("MTP ")]
    assert [g["variant"] for g in gate] == ["as configured",
                                            "weights through int8"]
    assert gate[0]["passes"] and gate[0]["gate"]["rms"] < 0.02
    assert [(r["variant"], r["prompt"]) for r in mtp] == [
        ("as configured", 40), ("as configured", 90),
        ("weights through int8", 40), ("weights through int8", 90)]
    for r in mtp[:2]:
        assert r["delivered"] >= 20 and r["second_rows"] + r["refused"] > 0
        assert r["verify_rows"]["rows"] >= r["second_rows"] + r["refused"]
        assert r["module_rows"]["rows"] > 0
        assert r["verify_rows"]["rms"] < 0.02
        assert r["module_rows"]["rms"] < 0.02
        assert r["streams_agree_share"] == 1.0 and r["first_parting"] == -1
    assert (copy / f"chiprun_out/gate_probe/{TINY_CELL}.mtp.json").exists()
