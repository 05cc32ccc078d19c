"""The ``dots3-note-ep8-l5`` configuration and the latent-attention
model's per-layer metrics: the file holds the catalog row's config but for
its three cuts and builds the model it describes, the indexer's cost
against a count by hand, the three readers on a synthetic run, the cell's
traffic as the issue names it, the cell at the tiny preset end to end in
a temporary copy, and ``tools/gate_probe_dsa.py`` at the tiny preset."""

import json
import math
import time
from pathlib import Path

import jax
import pytest

import bench_tiny
from benchmark.lib import device, harness, latent_cost, manifest
from benchmark.lib.model import build_model
from benchmark.lib.peaks import PEAKS
from benchmark.lib.run import Run
from benchmark.lib.trace import Reduction

ROOT = Path(__file__).resolve().parents[2]
CELL = "dots3-note-ep8-l5.longctx_open"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUTS = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
TINY_CELL = "dots3-note-tiny.tiny_open"

# the published names the reference reads, at the registry's tiny sizes:
# index_topk 8 and window 5, both far below the contexts
TINY = {
    "source": "tests", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "apply_mla_qkv_lora_rescale": True, "first_k_dense_replace": 1,
    "layer_types": ["full_attention", "full_attention",
                    "sliding_attention", "sliding_attention",
                    "sliding_attention"],
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 1e4, "swa_num_attention_heads": 2,
    "swa_q_lora_rank": 32, "swa_kv_lora_rank": 40,
    "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
    "swa_v_head_dim": 16, "swa_rope_theta": 5e2,
    "sliding_window_size": 5, "index_topk": 8, "index_n_heads": 4,
    "index_head_dim": 16, "num_experts_per_tok": 4,
    "n_routed_experts": 4, "experts_held_first": 4,
    "routed_scaling_factor": 1.0, "norm_topk_prob": True,
    "reduced": [], "assumed": {}, "kind": "serve",
    "reference": "dots3_note",
    "registry": {"name": "dots3-note-tiny",
                 "overrides": {"param_dtype": "bfloat16"}},
    "expect": {"num_layers": 5, "index_topk": 8, "experts_held": 4,
               "n_routed_experts": 16, "latent_row": 32,
               "window_row": 48},
    # float32 arithmetic on the bf16 weights: with 8 keys chosen of a
    # hundred, bf16 noise in the indexer's scores swaps a choice in most
    # rows and each swap is an eighth of a row's attention (rms 31 %;
    # 1.8 % with the selection off); at index_topk 2048 a swap at the
    # margin is one key of 2048
    "engine_config": {
        "dtype": "float32",
        "memory_config": {"num_blocks": 128, "block_size": 16},
        "max_context": 128,
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 32}},
    "server_config": {}, "logit_rms_tolerance": 0.05}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``bench_tiny``'s copy, and on top of it the tiny latent preset under
    the open-loop mix, added the way this PR adds the real one: a file, an
    entry each, and the cell's name at the end of the lists the real cell
    is on."""
    dst = bench_tiny.make_copy(tmp_path_factory.mktemp("bench_dots3"))
    (dst / "benchmark/configs/dots3-note-tiny.json").write_text(
        json.dumps(TINY))
    man = json.loads((dst / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "dots3-note-tiny", "source": "tests",
                           "file": "benchmark/configs/dots3-note-tiny.json",
                           "reduced": [], "why": "tiny preset"})
    man["workloads"].append({"name": TINY_CELL, "config": "dots3-note-tiny",
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
    return json.loads((ROOT / "benchmark/configs/dots3-note-ep8-l5.json")
                      .read_text())


def test_the_file_builds_the_model_it_describes():
    cfg = _config()
    model = build_model(cfg)
    m = model.mla
    assert cfg["reduced"] == CUTS
    assert cfg["published"] == {"num_hidden_layers": 46,
                                "n_routed_experts": 256,
                                "vocab_size": 152064}
    assert (model.num_layers, model.vocab_size, m.experts_held) == (
        cfg["num_hidden_layers"], cfg["vocab_size"],
        (cfg["experts_held_first"], cfg["n_routed_experts"]))
    for prefix, w in (("", m.full), ("swa_", m.window)):
        assert (w.q_lora_rank, w.kv_lora_rank, w.qk_nope_head_dim,
                w.qk_rope_head_dim, w.v_head_dim) == tuple(
            cfg[prefix + k] for k in ("q_lora_rank", "kv_lora_rank",
                                      "qk_nope_head_dim", "qk_rope_head_dim",
                                      "v_head_dim"))
    assert (m.full.num_heads, m.window.num_heads) == (
        cfg["num_attention_heads"], cfg["swa_num_attention_heads"])
    assert (m.full.rope_theta, m.window.rope_theta) == (
        cfg["rope_theta"], cfg["swa_rope_theta"])
    assert list(m.layer_types) == cfg["layer_types"]
    assert (m.sliding_window, m.index_topk, m.index_heads, m.index_head_dim,
            m.n_routed_experts, m.num_experts_per_tok,
            m.moe_intermediate_size, m.first_k_dense) == (
        cfg["sliding_window_size"], cfg["index_topk"], cfg["index_n_heads"],
        cfg["index_head_dim"], cfg["published"]["n_routed_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["first_k_dense_replace"])
    assert (model.hidden_size, model.intermediate_size,
            model.layernorm_eps, model.max_seq_len) == (
        cfg["hidden_size"], cfg["intermediate_size"], cfg["rms_norm_eps"],
        cfg["max_position_embeddings"])
    assert set(cfg["assumed"]) >= {
        "apply_mla_qkv_lora_rescale", "attention_gate_type", "indexer",
        "sliding_window_size", "routing", "e_score_correction_bias",
        "rope_layout", "initialisation"}
    # the engine's sizes: what a token and a sequence hold
    eng = cfg["engine_config"]
    mem, state = eng["memory_config"], eng["state_manager"]
    rows = mem["num_blocks"] * mem["block_size"]
    assert rows * 2 * (640 + 128) * 2 == 2304 * 2 ** 20       # the pages
    ring = math.ceil((513 + state["max_ragged_batch_size"]) / 128) * 128
    assert ring == 1664
    assert 3 * (state["max_tracked_sequences"] + 1) * ring * 1152 * 2 \
        == pytest.approx(361.97 * 2 ** 20, rel=1e-4)           # the rings
    assert eng["max_context"] // mem["block_size"] == 256


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_config_but_for_its_cuts():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"dots3-note-prev"' in line)
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "-") != v]
    assert sorted(differs) == sorted(CUTS) == sorted(cfg["reduced"])
    entry = next(c for c in manifest.load_manifest(ROOT)["configs"]
                 if c["name"] == "dots3-note-ep8-l5")
    assert entry["reduced"] == CUTS and entry["source"] == cfg["source"]


def test_the_cells_traffic_is_what_the_issue_names():
    cell = manifest.load_cell(ROOT, CELL)
    t = cell.traffic
    assert cell.chips == 1 and t["driver"] == "open_loop"
    assert t["prompt_tokens"]["min"] == 4096 > cell.config["index_topk"]
    assert t["prompt_tokens"]["max"] in (28672, 16384)
    assert t["answer_tokens"] == {"min": 64, "max": 512}
    assert (t["answer_follows_prompt"], t["block"], t["drain_s"]) == (
        False, 8, 60)
    assert t["prompt_tokens"]["max"] + 512 <= cell.config[
        "engine_config"]["max_context"]
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_mean_ms", "token_gap_mean_ms", "setup_s"}
    assert {"index_scores_roofline", "selected_share_p50",
            "device_idle_share.open",
            "pallas_time_share.open"} <= set(cell.readers)


def test_index_scores_cost_against_a_hand_count():
    # one layer, 2 heads of 4: 10 pairs, 3 rows, 7 keys in the contexts
    fl, by = latent_cost.index_scores_cost(10, 3, 7, layers=1, heads=2, dim=4)
    assert fl == 10 * 2 * (2 * 4)
    assert by == 10 * 4 + 7 * 4 * 2 + 3 * 2 * (4 * 2 + 4)
    # published widths, two layers: a 1024-row chunk on a 16k context
    # (rows 15360 .. 16383 see 15361 .. 16384 keys)
    pairs = sum(range(15361, 16385))
    fl, by = latent_cost.index_scores_cost(pairs, 1024, 16384, 2, 64, 128)
    assert fl == 2 * 2 * 128 * 64 * pairs
    s, bound = latent_cost.least_time(fl, by, PEAKS["TPU v5 lite"])
    assert bound == "compute" and s == pytest.approx(fl / 197e12)
    assert 2.6e-3 < s < 2.8e-3


def _synthetic_run(n_steps=40, traced_steps=10):
    lo = 1_000_000.0
    hi = lo + 20e6
    mid = lo + 7.5e6
    spans = []
    for i in range(n_steps):
        rows, ctx = 16 + i, 4096 + 16 * i
        spans.append({"ph": "X", "name": "v2.schedule",
                      "ts": mid + i * 0.25e6, "dur": 50.0,
                      "args": {"latent_rows": rows, "kv_rows": ctx,
                               "index_pairs": rows * ctx,
                               "selected_keys": rows * 2048,
                               "window_keys": rows * 513,
                               "expert_rows": rows}})
    spans.append({"ph": "X", "name": "v2.schedule", "ts": mid, "dur": 1.0,
                  "args": {"seqs": 0, "tokens": 0}})
    trace = Reduction(
        chips=1, window_s=5.0, busy_s=4.0, mosaic_s=0.5,
        mosaic_calls=float(2 * traced_steps), collective_s=0.0,
        exposed_collective_s=0.0,
        top_ops=[["fusion.1 fusion", 2.0], ["sort.1 sort", 0.3],
                 ["latent_index_scores.3 pallas", 0.3],
                 ["other_kernel.2 pallas", 0.1]],
        idle_gaps=[])
    return Run(correct=True, attempted=1, failed=0, end_to_end={},
               setup_s=1.0, spans=spans, trace=trace,
               counters={"window_mono_us": (lo, hi),
                         "model": build_model(_config()),
                         "device_kind": "TPU v5 lite"})


def test_the_two_readers_on_a_synthetic_run():
    cell = manifest.load_cell(ROOT, CELL)
    run = _synthetic_run()
    # every step reads 2048 of its 4096 + 16 i keys a row; the shares
    # fall with i, and the lower of the two middle values is step 20's
    assert cell.readers["selected_share_p50"](run, cell) \
        == pytest.approx(2048 / (4096 + 16 * 20))
    # the least work of 10 consecutive steps is that of the first ten;
    # the kernel's time is all Pallas time (0.5 s) less the other
    # kernel's 0.1 s by name, and it need not be among the ten
    # operations the table names
    got = cell.readers["index_scores_roofline"](run, cell)
    run.trace.top_ops = [["fusion.1 fusion", 2.0],
                         ["other_kernel.2 pallas", 0.1]]
    assert cell.readers["index_scores_roofline"](run, cell) == got
    first = [(16 + i, 4096 + 16 * i) for i in range(10)]
    fl, by = latent_cost.index_scores_cost(
        sum(r * c for r, c in first), sum(r for r, _ in first),
        sum(c for _, c in first), 2, 64, 128)
    want, _ = latent_cost.least_time(fl, by, PEAKS["TPU v5 lite"])
    assert got == pytest.approx(100 * want / 0.4)


def test_the_readers_find_nothing_on_another_program():
    """A run of a model without latent attention (and the parent
    commit's): no such kernel in the trace, no such argument in a span."""
    cell = manifest.load_cell(ROOT, CELL)
    run = _synthetic_run()
    for e in run.spans:
        e["args"] = {"seqs": 1, "tokens": 3, "kv_rows": 9}
    for name in ("index_scores_roofline", "selected_share_p50"):
        assert cell.readers[name](run, cell) is None
    run.trace = None
    assert cell.readers["index_scores_roofline"](run, cell) is None


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_a_tiny_latent_cell_end_to_end(copy, traced, plain_jit):
    out = harness.run_cell(copy, TINY_CELL, 2 ** 31 + 5, 1.5, traced,
                           time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    if not traced:
        assert set(out["metrics"]) == {"ttft_mean_ms", "token_gap_mean_ms",
                                       "setup_s"}
        return
    # the latent model's counters are read on the CPU too: its v2.schedule
    # spans carry them whatever the device; no device plane here, so the
    # readers of the trace find nothing and leave their metrics out
    assert set(out["metrics"]) == {
        "queue_wait_p50_ms.open", "serve_step_ms_p50.open",
        "prefill_tokens_per_s.open", "compiles_in_window.open",
        "ttft_p95_ms.open", "token_gap_p95_ms.open", "loadgen_late_p95_ms",
        "selected_share_p50", "steps_counted"}
    assert 0 < out["metrics"]["selected_share_p50"]["value"] < 1
    assert out["metrics"]["compiles_in_window.open"]["value"] == 0


def test_gate_probe_dsa_at_the_tiny_preset(copy, capsys):
    """The probe's variants on the tiny cell of a temporary copy: as
    configured the long prompt, the reference attending over the sets
    tapped out of the program's steps, reads as the short ones do, and
    both full layers' sets are the reference's own; the latent rows
    through int8 read worse."""
    probe = manifest.load_code(copy, "tools", "gate_probe_dsa")
    assert probe.main(["dots3-note-tiny.tiny_open", "100", "5", "own=1"],
                      root=copy, need_chip=False) == 0
    rows = [json.loads(line[5:]) for line in capsys.readouterr().out
            .splitlines() if line.startswith("GATE ")]
    by = {}
    for r in rows:
        by.setdefault((r["variant"], str(r["prompt"])), []).append(r)
    assert {k: len(v) for k, v in by.items()} == {
        ("as configured", "[300, 290]"): 1, ("as configured", "100"): 1,
        ("latent rows through int8", "[300, 290]"): 3,
        ("expert weights through int8", "[300, 290]"): 2,
        ("weights through int8", "[300, 290]"): 2}
    long_, = by["as configured", "100"]
    assert long_["passes"] and long_["positions"] == 9
    assert long_["overlap_mean"] == long_["overlap_min"] == [1.0, 1.0]
    # the same sets: attending over the program's or its own is one reading
    assert long_["rms_own_sets"] == pytest.approx(long_["rms"], rel=1e-3)
    base, = by["as configured", "[300, 290]"]
    # with 8 keys chosen of a hundred a rounded row swaps choices, more in
    # one reading than in another (2-19 % over seeds): each reads far
    # worse than the engine as configured, and not every one is passed
    int8_rows = by["latent rows through int8", "[300, 290]"]
    assert all(r["rms"] > 100 * base["rms"] for r in int8_rows)
    assert not all(r["passes"] for r in int8_rows)
    assert (copy / "chiprun_out/gate_probe/dots3-note-tiny.tiny_open"
            ".dsa.json").exists()
