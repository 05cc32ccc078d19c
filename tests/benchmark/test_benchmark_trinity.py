"""The ``trinity-large-ep8-l5`` configuration and the paged cache's
per-layer metrics: the file holds the catalog row's config but for its
five cuts and builds the model it describes (the arithmetic of
``reduced_why`` from the program's own shapes), the cell's traffic as the
issue names it, the three readers on recorded spans and on another
program's, the cell at the tiny preset end to end in a temporary copy (all
three new metrics on the traced line), and ``tools/gate_probe_window.py``
at the tiny preset."""

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
CELL = "trinity-large-ep8-l5.mixed_len_open"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUTS = ["num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size", "layer_types"]
TINY_CELL = "trinity-tiny.tiny_long_open"
NEW = ["window_read_share_p50", "kv_held_share_p50", "pages_freed_per_s"]

# the published names the reference reads, at the registry's tiny sizes:
# window 24, far below the contexts
TINY = {
    "source": "tests", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "num_dense_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1e4,
    "sliding_window": 24, "mup_enabled": True,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "num_experts_per_tok": 4, "num_experts": 4, "experts_held_first": 4,
    "route_scale": 2.448, "route_norm": True,
    "reduced": [], "assumed": {}, "kind": "serve", "reference": "afmoe",
    "registry": {"name": "trinity-tiny",
                 "overrides": {"param_dtype": "bfloat16"}},
    "expect": {"num_layers": 8, "experts_held": 4, "n_routed_experts": 16,
               "num_dense_layers": 2, "window_layers": 6,
               "layer_window": 24, "route_scale": 2.448},
    # float32 arithmetic on the bf16 weights: 1e-5 as configured, 1e-2
    # with the weights through int8
    "engine_config": {
        "dtype": "float32",
        "memory_config": {"num_blocks": 128, "window_blocks": 48,
                          "block_size": 8},
        "max_context": 128,
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 16,
                          "min_context_blocks": 16}},
    "server_config": {}, "logit_rms_tolerance": 0.002}
# prompts past the window, so that pages are freed inside the window
TINY_TRAFFIC = {"driver": "open_loop", "rate_per_s": 5.0,
                "prompt_tokens": {"min": 30, "max": 90},
                "answer_tokens": {"min": 3, "max": 8},
                "answer_follows_prompt": False, "block": 4, "base_seed": 11,
                "drain_s": 60}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``bench_tiny``'s copy, and on top of it the tiny Trinity preset
    under an open-loop mix of its own, added the way this PR adds the real
    one: a file each, an entry each, and the cell's name at the end of the
    lists the real cell is on."""
    dst = bench_tiny.make_copy(tmp_path_factory.mktemp("bench_trinity"))
    (dst / "benchmark/configs/trinity-tiny.json").write_text(
        json.dumps(TINY))
    (dst / "benchmark/traffic/tiny_long_open.json").write_text(
        json.dumps(TINY_TRAFFIC))
    man = json.loads((dst / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "trinity-tiny", "source": "tests",
                           "file": "benchmark/configs/trinity-tiny.json",
                           "reduced": [], "why": "tiny preset"})
    man["workloads"].append({"name": TINY_CELL, "config": "trinity-tiny",
                             "traffic": "tiny_long_open", "chips": 1,
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
    return json.loads((ROOT / "benchmark/configs/trinity-large-ep8-l5.json")
                      .read_text())


def test_the_file_builds_the_model_it_describes():
    cfg = _config()
    model = build_model(cfg)
    mx = model.mixed
    assert cfg["reduced"] == CUTS
    assert {k: cfg["published"][k] for k in CUTS[:4]} == {
        "num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
        "vocab_size": 200192}
    assert (model.num_layers, model.vocab_size, mx.experts_held,
            mx.num_dense_layers) == (
        cfg["num_hidden_layers"], cfg["vocab_size"],
        (cfg["experts_held_first"], cfg["num_experts"]),
        cfg["num_dense_layers"])
    assert list(mx.layer_types[:5]) == cfg["layer_types"] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert mx.kinds(5) == ((False, False), (False, True), (False, True),
                           (True, True), (False, True))
    assert (model.hidden_size, model.num_heads, model.kv_heads,
            model.dim_per_head, model.intermediate_size,
            model.layernorm_eps, model.rope_theta, model.max_seq_len) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["intermediate_size"], cfg["rms_norm_eps"], cfg["rope_theta"],
        cfg["max_position_embeddings"])
    assert (mx.sliding_window, mx.n_routed_experts, mx.num_experts_per_tok,
            mx.moe_intermediate_size, mx.n_shared_experts,
            mx.route_scale) == (
        cfg["sliding_window"], cfg["published"]["num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["num_shared_experts"], cfg["route_scale"])
    assert (mx.qk_norm, mx.gate, mx.sandwich_norm, mx.rope_full) == (
        True, True, True, False)
    assert mx.embed_multiplier == cfg["hidden_size"] ** 0.5 \
        and cfg["mup_enabled"] and cfg["route_norm"]
    assert (cfg["n_group"], cfg["topk_group"], cfg["score_func"]) == (
        1, 1, "sigmoid")
    assert set(cfg["assumed"]) >= {
        "attention_gate", "qk_norm", "rotary", "sliding_window",
        "embedding", "sandwich_norm", "routing", "initialisation"}
    # the arithmetic of reduced_why, from the program's own shapes
    from deepspeed_tpu.models import transformer as tf_model

    shapes = jax.eval_shape(lambda k: tf_model.init_params(model, k),
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    layers = shapes["layers"]
    attn = count(layers["attn"]) // 5
    assert attn == 3 * 3072 * 6144 + 2 * 3072 * 1024 + 2 * 128
    assert count(layers["mlp"]) == 3 * 3072 * 12288
    expert = 3 * 3072 * 3072
    assert count(layers["moe"]) == 4 * (33 * expert + 3072 * 256 + 256)
    assert count(shapes["embed"]) + count(shapes["lm_head"]) \
        == 2 * 25024 * 3072
    total = count(shapes)
    assert total == 4_321_903_872
    for said in ("4,321,903,872", "8.05 GiB", "62.91 M", "113.25 M",
                 "28.31 M", "998.0 M", "153.8 M"):
        assert said in cfg["reduced_why"], said
    assert round(total * 2 / 2 ** 30, 2) == 8.05
    # the engine: two pools, and what a token holds in each
    eng = cfg["engine_config"]
    mem, state = eng["memory_config"], eng["state_manager"]
    a_token = 2 * model.kv_heads * model.dim_per_head * 2      # one layer
    assert a_token == 4096 and mem["block_size"] == 128
    assert mem["num_blocks"] * 128 * a_token * 1 == 2 ** 30
    assert mem["window_blocks"] * 128 * a_token * 4 == 1.5 * 2 ** 30
    assert eng["max_context"] == 32768
    # ONE block-table width: every step program is compiled once a token
    # bucket (PERF.md section 4: the compile cache)
    assert state["min_context_blocks"] * 128 == eng["max_context"]
    assert -(-(4096 + state["max_ragged_batch_size"]) // 128) + 1 == 41
    assert cfg["server_config"] == {}


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_config_but_for_its_cuts():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Trinity-Large-Preview")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "-") != v]
    assert sorted(differs) == sorted(CUTS) == sorted(cfg["reduced"])
    assert cfg["layer_types"] == row["config"]["layer_types"][:5]
    entry = next(c for c in manifest.load_manifest(ROOT)["configs"]
                 if c["name"] == "trinity-large-ep8-l5")
    assert entry["reduced"] == CUTS and entry["source"] == cfg["source"]


def test_the_cells_traffic_is_what_the_issue_names():
    cell = manifest.load_cell(ROOT, CELL)
    t = cell.traffic
    assert cell.chips == 1 and t["driver"] == "open_loop"
    assert t["prompt_tokens"] == {"min": 512, "max": 30720}
    assert t["answer_tokens"] == {"min": 128, "max": 1024}
    assert (t["answer_follows_prompt"], t["block"], t["drain_s"]) == (
        False, 8, 60)
    others = {json.loads(p.read_text()).get("base_seed")
              for p in (ROOT / "benchmark/traffic").glob("*.json")
              if p.stem != "mixed_len_open"}
    assert t["base_seed"] not in others
    assert 30720 + 1024 <= cell.config["engine_config"]["max_context"]
    # half of the knee 1.5/s (ISSUE 46's first fallback), over its floor of
    # 32 requests a window
    assert t["rate_per_s"] == 0.75 and round(t["rate_per_s"] * 51) == 38
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_mean_ms", "token_gap_mean_ms", "setup_s"}
    assert set(cell.readers) == {
        "queue_wait_p50_ms.open", "serve_step_ms_p50.open",
        "prefill_tokens_per_s.open", "compiles_in_window.open",
        "pallas_time_share.open", "device_idle_share.open",
        "loadgen_late_p95_ms", "ttft_p95_ms.open", "token_gap_p95_ms.open",
        *NEW}
    by = {m["name"]: m for m in cell.per_layer}
    assert [(n, by[n]["layer"], by[n]["moves"], by[n]["better"],
             by[n]["unit"], by[n]["workloads"]) for n in NEW] == [
        ("window_read_share_p50", "paged cache", "token_gap_mean_ms",
         "lower", "ratio", [CELL]),
        ("kv_held_share_p50", "paged cache", "ttft_mean_ms", "lower",
         "ratio", [CELL]),
        ("pages_freed_per_s", "paged cache", "token_gap_mean_ms", "higher",
         "count/s", [CELL])]
    man = manifest.load_manifest(ROOT)
    assert len(man["workloads"]) == 8 and man["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert [m["name"] for m in man["per_layer"][-3:]] == NEW


def _recorded_run(model):
    """Spans as a traced run records them: five steps in the window (one
    before any context passed the window), one outside it."""
    lo, hi = 1_000_000.0, 11_000_000.0
    steps = [  # full_kv, window_kv, full_pages, window_pages, freed
        (300, 300, 3, 3, 0), (9000, 4396, 72, 38, 1), (20000, 8192, 160, 70,
                                                       4),
        (30000, 8492, 240, 75, 0), (40000, 12288, 320, 100, 5)]
    spans = [{"ph": "X", "name": "v2.schedule", "ts": lo + 1e6 + i * 1e5,
              "dur": 40.0,
              "args": {"seqs": 3, "tokens": 20, "full_kv_rows": a,
                       "window_kv_rows": b, "full_pages": c,
                       "window_pages": d, "pages_freed": e,
                       "expert_rows": 10.0}}
             for i, (a, b, c, d, e) in enumerate(steps)]
    spans.append({"ph": "X", "name": "v2.schedule", "ts": hi + 9.0,
                  "dur": 1.0, "args": dict(spans[-1]["args"],
                                           pages_freed=1000)})
    spans.append({"ph": "X", "name": "v2.schedule", "ts": lo + 5.0,
                  "dur": 1.0, "args": {"seqs": 0, "tokens": 0}})
    return Run(correct=True, attempted=1, failed=0, end_to_end={},
               setup_s=1.0, spans=spans,
               counters={"window_mono_us": (lo, hi), "window_s": 10.0,
                         "model": model})


def test_the_three_readers_on_recorded_spans_and_on_another_program():
    cell = manifest.load_cell(ROOT, CELL)
    model = build_model(cell.config)
    run = _recorded_run(model)
    assert cell.readers["window_read_share_p50"](run, cell) \
        == pytest.approx(8192 / 20000)
    # one full layer and four window layers of five
    assert cell.readers["kv_held_share_p50"](run, cell) \
        == pytest.approx((160 + 4 * 70) / (5 * 160))
    assert cell.readers["pages_freed_per_s"](run, cell) == 1.0
    # another model's program (and the parent commit's): no such argument
    # in a span, and nothing is raised
    for e in run.spans:
        e["args"] = {"seqs": 1, "tokens": 3, "kv_rows": 9}
    for name in NEW:
        assert cell.readers[name](run, cell) is None
    run.spans = []
    for name in NEW:
        assert cell.readers[name](run, cell) is None


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_a_tiny_cell_that_frees_pages_end_to_end(copy, traced, plain_jit):
    out = harness.run_cell(copy, TINY_CELL, 2 ** 31 + 7, 1.5, traced,
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
        *NEW, "steps_counted"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["pages_freed_per_s"] > 0
    assert 0 < m["window_read_share_p50"] < 1
    assert 0 < m["kv_held_share_p50"] < 1
    # the warm-up's steps ran the programs the serve loop used
    assert m["compiles_in_window.open"] == 0


def test_gate_probe_window_at_the_tiny_preset(copy, capsys, plain_jit):
    """The probe on the tiny cell of a temporary copy, float32 arithmetic:
    prompts past the window in chunks of 16 agree with the reference, the
    spans hold the mechanism, and with every matrix through int8 (the
    threshold for "a matmul weight" lowered to the tiny preset's sizes)
    every reading is refused."""
    manifest.load_code(copy, "tools", "gate_probe").BIG = 1 << 10
    probe = manifest.load_code(copy, "tools", "gate_probe_window")
    assert probe.main([TINY_CELL, "5", "long=60,100"], root=copy,
                      need_chip=False) == 0
    rows = [json.loads(x[5:]) for x in capsys.readouterr().out.splitlines()
            if x.startswith("GATE ")]
    assert [(r["variant"], r["prompt"]) for r in rows] == [
        ("as configured", [300, 290]), ("as configured", 60),
        ("as configured", 100), ("weights through int8", [300, 290]),
        ("weights through int8", 60), ("weights through int8", 100)]
    assert all(r["passes"] and r["rms"] < 2e-4 for r in rows[:3])
    assert not any(r["passes"] for r in rows[3:])
    # (n + 8 - 1 - 24) // 8 pages gone by the last step; never more than
    # ceil((24 + 16) / 8) + 1 held
    assert [r["pages_freed"] for r in rows[1:3]] == [5, 10]
    assert all(r["window_pages_max"] <= 6 for r in rows[1:3])
    assert rows[2]["full_pages_max"] == 14
    assert (copy / f"chiprun_out/gate_probe/{TINY_CELL}.window.json"
            ).exists()
