"""The ``mimo-v2-flash-ep16-l7`` configuration, its cell and its two
per-layer metrics: the file holds the catalog row's config but for its
five cuts and builds the model it describes (the arithmetic of
``reduced_why`` from the program's own shapes), the cell's traffic as the
issue names it, ``lib/mixed_cost.py`` against a hand count, the two
readers on recorded spans and on another program's, ``kernel_calls_per_step``
against the step program's jaxpr, the cell at the tiny preset end to end
in a temporary copy, and ``tools/gate_probe_mimo.py`` at the tiny preset.
Every assertion on the manifest is by NAME or by membership, never by
place or count: a later PR appends behind these entries."""

import json
import time
from pathlib import Path

import jax
import pytest

import bench_tiny
from benchmark.lib import device, harness, manifest, mixed_cost
from benchmark.lib.model import build_model
from benchmark.lib.run import Run
from benchmark.lib.trace import Reduction

ROOT = Path(__file__).resolve().parents[2]
CONFIG = "mimo-v2-flash-ep16-l7"
CELL = f"{CONFIG}.agent_mixed_open"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUTS = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
TINY_CELL = "mimo-tiny.tiny_agent_open"
NEW = ["paged_read_roofline", "kv_bytes_held_share_p50",
       "held_expert_rows_p50"]
JOINED = ["window_read_share_p50", "pages_freed_per_s"]
OPEN = ["queue_wait_p50_ms.open", "serve_step_ms_p50.open",
        "prefill_tokens_per_s.open", "compiles_in_window.open",
        "pallas_time_share.open", "device_idle_share.open",
        "loadgen_late_p95_ms", "ttft_p95_ms.open", "token_gap_p95_ms.open"]

# the published names the reference reads, at the registry's tiny sizes:
# window 24, far below the contexts
TINY = {
    "source": "tests", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "vocab_size": 512, "layernorm_epsilon": 1e-5,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "rope_theta": 5e6, "swa_rope_theta": 1e4,
    "partial_rotary_factor": 0.334, "sliding_window": 24,
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "num_experts_per_tok": 2, "n_routed_experts": 4,
    "experts_held_first": 4, "norm_topk_prob": True,
    "routed_scaling_factor": None,
    "reduced": [], "assumed": {}, "kind": "serve",
    "reference": "mimo_v2_flash",
    "registry": {"name": "mimo-tiny",
                 "overrides": {"param_dtype": "bfloat16"}},
    "expect": {"num_layers": 4, "experts_held": 4, "n_routed_experts": 16,
               "kv_heads": 2, "window_kv_heads": 4, "dim_per_head": 24,
               "value_width": 16, "window_layers": 2, "sink_layers": 2,
               "layer_window": 24},
    # float32 arithmetic on the bf16 weights
    "engine_config": {
        "dtype": "float32",
        "memory_config": {"num_blocks": 128, "window_blocks": 48,
                          "block_size": 8},
        "max_context": 128,
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 16,
                          "min_context_blocks": 16}},
    "server_config": {}, "logit_rms_tolerance": 0.0005}
# prompts past the window, so that pages are freed inside the window
TINY_TRAFFIC = {"driver": "open_loop", "rate_per_s": 5.0,
                "prompt_tokens": {"min": 30, "max": 90},
                "answer_tokens": {"min": 3, "max": 8},
                "answer_follows_prompt": False, "block": 4, "base_seed": 13,
                "drain_s": 60}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``bench_tiny``'s copy, and on top of it the tiny preset under an
    open-loop mix of its own, added the way this PR adds the real one: a
    file each, an entry each, and the cell's name at the end of the lists
    the real cell is on."""
    dst = bench_tiny.make_copy(tmp_path_factory.mktemp("bench_mimo"))
    (dst / "benchmark/configs/mimo-tiny.json").write_text(json.dumps(TINY))
    (dst / "benchmark/traffic/tiny_agent_open.json").write_text(
        json.dumps(TINY_TRAFFIC))
    man = json.loads((dst / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "mimo-tiny", "source": "tests",
                           "file": "benchmark/configs/mimo-tiny.json",
                           "reduced": [], "why": "tiny preset"})
    man["workloads"].append({"name": TINY_CELL, "config": "mimo-tiny",
                             "traffic": "tiny_agent_open", "chips": 1,
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
    return json.loads((ROOT / f"benchmark/configs/{CONFIG}.json")
                      .read_text())


def test_the_file_builds_the_model_it_describes():
    cfg = _config()
    model = build_model(cfg)
    mx = model.mixed
    assert cfg["reduced"] == CUTS
    assert {k: cfg["published"][k] for k in ("num_hidden_layers",
                                             "n_routed_experts",
                                             "vocab_size")} == {
        "num_hidden_layers": 48, "n_routed_experts": 256,
        "vocab_size": 152576}
    assert (model.num_layers, model.vocab_size, mx.experts_held) == (
        cfg["num_hidden_layers"], cfg["vocab_size"],
        (cfg["experts_held_first"], cfg["n_routed_experts"]))
    kinds = mx.kinds(model.num_layers)
    assert [int(not full) for full, _ in kinds] \
        == cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert [int(e) for _, e in kinds] == cfg["moe_layer_freq"] \
        == [0, 1, 1, 1, 1, 1, 1]
    assert (model.hidden_size, model.num_heads, model.kv_heads,
            model.window_kv_heads, model.dim_per_head, model.value_width,
            model.intermediate_size, model.layernorm_eps, model.rope_theta,
            mx.window_rope_theta, model.rotary_pct, model.max_seq_len) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
        cfg["head_dim"], cfg["v_head_dim"], cfg["intermediate_size"],
        cfg["layernorm_epsilon"], cfg["rope_theta"], cfg["swa_rope_theta"],
        cfg["partial_rotary_factor"], cfg["max_position_embeddings"])
    assert int(192 * model.rotary_pct) == 64
    assert (mx.sliding_window, mx.n_routed_experts, mx.num_experts_per_tok,
            mx.moe_intermediate_size, mx.n_shared_experts, mx.route_scale,
            mx.value_scale, mx.window_sink, mx.full_sink) == (
        cfg["sliding_window"], cfg["published"]["n_routed_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"], 0, 1.0,
        cfg["attention_value_scale"], cfg["add_swa_attention_sink_bias"],
        cfg["add_full_attention_sink_bias"])
    assert cfg["n_shared_experts"] is None \
        and cfg["routed_scaling_factor"] is None
    # Trinity's q/k norms, gate, sandwich norms and muP embedding: all off
    assert (mx.qk_norm, mx.gate, mx.sandwich_norm, mx.rope_full,
            mx.embed_multiplier) == (False, False, False, True, 1.0)
    assert (cfg["n_group"], cfg["topk_group"], cfg["scoring_func"]) == (
        1, 1, "sigmoid")
    assert set(cfg["assumed"]) >= {
        "sink", "attention_value_scale", "rotary", "sliding_window",
        "routing", "initialisation", "left_out"}
    # the arithmetic of reduced_why, from the program's own shapes
    from deepspeed_tpu.models import transformer as tf_model

    shapes = jax.eval_shape(lambda k: tf_model.init_params(model, k),
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    layers = shapes["layers"]
    full = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    window = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096 + 64
    assert count(layers["attn_full"]) == 2 * full
    assert count(layers["attn_window"]) == 5 * window
    assert count(layers["mlp"]) == 3 * 4096 * 16384
    expert = 3 * 4096 * 2048
    assert count(layers["moe"]) == 6 * (16 * expert + 4096 * 256 + 256)
    assert count(shapes["embed"]) + count(shapes["lm_head"]) \
        == 2 * 19072 * 4096
    total = count(shapes)
    assert total == 3_429_955_392
    for said in ("3,429,955,392", "6.39 GiB", "89.13 M", "94.37 M",
                 "201.33 M", "25.17 M", "498.07 M", "156.24 M"):
        assert said in cfg["reduced_why"], said
    assert round(total * 2 / 2 ** 30, 2) == 6.39
    assert (round(full / 1e6, 2), round(window / 1e6, 2)) == (89.13, 94.37)
    # the engine: two pools of two shapes, and what a token holds in each
    # AS LAID OUT (a key row in 256 lanes)
    eng = cfg["engine_config"]
    mem, state = eng["memory_config"], eng["state_manager"]
    assert mem["block_size"] == 128 == mx.sliding_window
    assert mem["num_blocks"] * 128 * 4 * (256 + 128) * 2 * 2 \
        == 1.875 * 2 ** 30
    assert eng["max_context"] == 20480 + 128     # a page for the warm-up
    assert state["min_context_blocks"] * 128 == eng["max_context"]
    per_seq = -(-(128 + state["max_ragged_batch_size"]) // 128) + 1
    assert per_seq == 10
    # every tracked sequence decoding (3 pages) beside two prompts in
    # their chunks
    assert mem["window_blocks"] > state["max_tracked_sequences"] * 3 \
        + 2 * per_seq
    assert cfg["server_config"] == {}
    assert cfg["attention_impl"] == "paged_pallas"


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_config_but_for_its_cuts():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "MiMo-V2-Flash")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "-") != v]
    assert sorted(differs) == sorted(CUTS) == sorted(cfg["reduced"])
    # layer 0 and the period of layers 6-11
    pattern = row["config"]["hybrid_layer_pattern"]
    assert cfg["hybrid_layer_pattern"] == pattern[:1] + pattern[6:12]
    entry = next(c for c in manifest.load_manifest(ROOT)["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == CUTS and entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_cells_traffic_is_what_the_issue_names():
    cell = manifest.load_cell(ROOT, CELL)
    t = cell.traffic
    assert cell.chips == 1 and t["driver"] == "open_loop"
    assert t["prompt_tokens"] == {"min": 1024, "max": 16384}
    assert t["answer_tokens"] == {"min": 256, "max": 4096}
    assert (t["answer_follows_prompt"], t["block"], t["drain_s"]) == (
        False, 8, 60)
    others = {json.loads(p.read_text()).get("base_seed")
              for p in (ROOT / "benchmark/traffic").glob("*.json")
              if p.stem != "agent_mixed_open"}
    assert t["base_seed"] not in others
    assert 16384 + 4096 <= cell.config["engine_config"]["max_context"]
    assert 0 < t["rate_per_s"] and "knee" in t["why"]
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_mean_ms", "token_gap_mean_ms", "setup_s"}
    assert set(cell.readers) == {*OPEN, *JOINED, *NEW}
    by = {m["name"]: m for m in cell.per_layer}
    assert [(n, by[n]["layer"], by[n]["moves"], by[n]["better"],
             by[n]["unit"], by[n]["source"], by[n]["workloads"])
            for n in NEW] == [
        ("paged_read_roofline", "attention kernels", "token_gap_mean_ms",
         "higher", "%", "device_trace", [CELL]),
        ("kv_bytes_held_share_p50", "paged cache", "ttft_mean_ms", "lower",
         "ratio", "program_counter", [CELL]),
        ("held_expert_rows_p50", "experts", "token_gap_mean_ms", "higher",
         "rows", "program_counter", [CELL])]
    man = manifest.load_manifest(ROOT)
    mine = [w for w in man["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in mine] == [CELL]
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    listed = {m["name"] for m in man["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {*OPEN, *JOINED, *NEW}
    # the two it does NOT join: pages counted alike cannot say what two
    # shapes hold; the experts' metric is another cell's alone
    assert not {"kv_held_share_p50", "expert_rows_per_held_p50"} & listed


def test_mixed_cost_against_a_hand_count():
    # one layer of a kind: 10 live pairs, 7 key rows, 3 query rows, 64
    # heads, 4 KV heads, 192 / 128
    fl, by = mixed_cost.read_cost(10, 7, 3, 1, 64, 4, 192, 128)
    assert fl == 2 * 320 * 64 * 10
    assert by == 2 * (4 * 320 * 7 + 64 * 320 * 3)
    alloc = {"full_kv_heads": 4, "window_kv_heads": 8, "key_width": 192,
             "value_width": 128}
    steps = [{"full_qk_pairs": 1000, "window_qk_pairs": 128,
              "full_kv_rows": 1000, "window_kv_rows": 128, "tokens": 1},
             {"full_qk_pairs": 50, "window_qk_pairs": 50,
              "full_kv_rows": 10, "window_kv_rows": 10, "tokens": 10}]
    fl, by = mixed_cost.step_cost(steps, alloc, 64, window_layers=5,
                                  full_layers=2)
    assert fl == 2 * 320 * 64 * (1050 * 2 + 178 * 5)
    assert by == 2 * (2 * (4 * 320 * 1010 + 64 * 320 * 11)
                      + 5 * (8 * 320 * 138 + 64 * 320 * 11))


ALLOC = {"full_pool_bytes": 1, "window_pool_bytes": 1, "window_layers": 5,
         "full_page_bytes": 393216, "window_page_bytes": 786432,
         "full_kv_heads": 4, "window_kv_heads": 8, "key_width": 192,
         "value_width": 128, "sink_layers": 5, "kernel_calls_per_step": 14}


def _recorded_run(model, traced=True):
    """Spans as a traced run records them: the ``v2.state_alloc`` span
    and six decode steps of one stream at context 8001.. inside the
    window's middle stretch, one step outside the window; a trace of
    THREE steps (42 Pallas calls at 14 a step) whose table names the
    paged kernel and the append."""
    lo, hi = 1_000_000.0, 11_000_000.0
    spans = [{"ph": "X", "name": "v2.state_alloc", "ts": 5.0, "dur": 1.0,
              "args": dict(ALLOC)}]
    for i in range(6):
        ctx = 8001 + i
        spans.append({
            "ph": "X", "name": "v2.schedule", "ts": lo + 3e6 + i * 1e5,
            "dur": 40.0,
            "args": {"seqs": 1, "tokens": 1, "full_kv_rows": ctx,
                     "window_kv_rows": 128, "full_qk_pairs": ctx,
                     "window_qk_pairs": 128, "full_pages": 63 + i,
                     "window_pages": 2, "pages_freed": 0,
                     "expert_rows": 0.5 + i}})
    spans.append({"ph": "X", "name": "v2.schedule", "ts": hi + 9.0,
                  "dur": 1.0, "args": dict(spans[-1]["args"],
                                           full_pages=10 ** 6)})
    trace = Reduction(
        chips=1, window_s=5.0, busy_s=4.0, mosaic_s=0.010, mosaic_calls=42.0,
        collective_s=0.0, exposed_collective_s=0.0,
        top_ops=[["fusion.1 fusion", 2.0], ["paged_qblock.3 pallas", 0.006],
                 ["kv_append.5 pallas", 0.002]], idle_gaps=[]) \
        if traced else None
    return Run(correct=True, attempted=1, failed=0, end_to_end={},
               setup_s=1.0, spans=spans, trace=trace,
               counters={"window_mono_us": (lo, hi), "window_s": 10.0,
                         "model": model, "device_kind": "TPU v5 lite"})


def test_the_new_readers_on_recorded_spans_and_on_another_program(capsys):
    cell = manifest.load_cell(ROOT, CELL)
    model = build_model(cell.config)
    run = _recorded_run(model)
    # the least work of three consecutive steps is the first three's;
    # bytes bound it: K and V at the published widths, q and o rows once
    ctx = 8001 + 8002 + 8003
    by = 2 * (2 * (4 * 320 * ctx + 64 * 320 * 3)
              + 5 * (8 * 320 * 3 * 128 + 64 * 320 * 3))
    fl = 2 * 320 * 64 * (2 * ctx + 5 * 3 * 128)
    assert by / 819e9 > fl / 197e12
    # the paged kernels' time: all Pallas time less the append's
    got = cell.readers["paged_read_roofline"](run, cell)
    assert got == pytest.approx(100 * (by / 819e9) / (0.010 - 0.002))
    assert "3 ragged steps in the trace" in capsys.readouterr().out
    # bytes held: (2 x 65 x 384 KiB + 5 x 2 x 768 KiB) / (7 x 65 x 768 KiB)
    # (no interpolation: lib/stats.py:percentile takes the sample at rank
    # int(0.5 x 5) of the six sorted shares, full_pages 66's)
    share = lambda f: (2 * f * 393216 + 5 * 2 * 786432) / (7 * f * 786432)
    assert cell.readers["kv_bytes_held_share_p50"](run, cell) \
        == pytest.approx(sorted(share(63 + i) for i in range(6))[2])
    # rows a held expert: expert_rows 0.5 .. 5.5 over the 16 held, the
    # sample at rank 2
    assert cell.readers["held_expert_rows_p50"](run, cell) \
        == pytest.approx(2.5 / 16)
    # an untraced run: the trace's reader finds nothing
    assert cell.readers["paged_read_roofline"](
        _recorded_run(model, traced=False), cell) is None
    # a trace whose table names no paged kernel
    run.trace.top_ops = [["ssd_ragged.2 pallas", 0.01]]
    assert cell.readers["paged_read_roofline"](run, cell) is None
    # another model's program (and the parent commit's): no such argument
    # in a span, and nothing is raised
    run = _recorded_run(model)
    for e in run.spans:
        e["args"] = {"seqs": 1, "tokens": 3, "kv_rows": 9,
                     "window_pages": 2, "full_pages": 3}
    for name in NEW:
        assert cell.readers[name](run, cell) is None
    run.spans = []
    for name in NEW:
        assert cell.readers[name](run, cell) is None


def _pallas_calls(jaxpr, times=1):
    """``pallas_call`` equations a jaxpr EXECUTES: one inside a scan
    counts once a trip (a jitted kernel called by two layers is ONE
    sub-jaxpr in the text, and two calls here)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += times
        inner = times * eqn.params["length"] \
            if eqn.primitive.name == "scan" else times
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _pallas_calls(sub, inner)
    return n


def test_the_program_counts_its_own_kernel_calls(monkeypatch):
    """``kernel_calls_per_step`` of ``v2.state_alloc`` against the step
    program's jaxpr, with the kernels' path pinned and interpreted (off
    the chip ``auto`` resolves to the XLA path and the count is 0): the
    append and the read a layer, each kind by its own shapes.  A head of
    128 / values 128 so that the append has a kernel for the rows."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.ops.pallas import paged_attention

    monkeypatch.setattr(paged_attention, "INTERPRET", True)
    engine = {"dtype": "float32",
              "memory_config": {"num_blocks": 16, "window_blocks": 16,
                                "block_size": 8},
              "max_context": 64,
              "state_manager": {"max_tracked_sequences": 2,
                                "max_ragged_batch_size": 16}}
    kw = dict(hidden_size=32, head_dim=128, v_head_dim=128, num_heads=2,
              num_kv_heads=1, window_kv_heads=2, intermediate_size=32,
              moe_intermediate_size=16, vocab_size=64)
    for impl, per_layer in (("paged_pallas", 2), ("paged_xla", 0)):
        model = get_model_config("mimo-tiny",
                                 v2_modules=(("attention", impl),), **kw)
        eng = InferenceEngineV2(model, dict(engine))
        alloc = eng._state_alloc
        fn, args = eng.audit_step_args("decode")
        in_program = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
        assert alloc["kernel_calls_per_step"] == 4 * per_layer == in_program
        assert (alloc["full_kv_heads"], alloc["window_kv_heads"],
                alloc["key_width"], alloc["value_width"],
                alloc["sink_layers"], alloc["window_layers"]) \
            == (1, 2, 128, 128, 2, 2)
        assert alloc["full_page_bytes"] == 1 * 8 * 256 * 4
        assert alloc["window_page_bytes"] == 2 * 8 * 256 * 4


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_the_tiny_cell_end_to_end(copy, traced, plain_jit):
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
        *JOINED, "kv_bytes_held_share_p50", "held_expert_rows_p50",
        "steps_counted"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["pages_freed_per_s"] > 0
    assert 0 < m["window_read_share_p50"] < 1
    # a window page is twice a full page here too (4 KV heads to 2)
    assert 0 < m["kv_bytes_held_share_p50"] < 1
    # rows x 2 of 16 a held expert: at most the 16-row budget's
    assert 0 < m["held_expert_rows_p50"] <= 16 * 2 / 16
    assert m["compiles_in_window.open"] == 0


def test_gate_probe_mimo_at_the_tiny_preset(copy, capsys, plain_jit):
    """The probe on the tiny cell of a temporary copy, float32
    arithmetic: prompts past the window in chunks of 16 agree with the
    reference and the spans hold the mechanism; the sinks zeroed, with the
    sign flipped and on other heads and the values unscaled IN THE PROGRAM
    are each refused by the tiny cell's
    tolerance, as is every matrix through int8 (the threshold for "a
    matmul weight" lowered to the tiny preset's sizes)."""
    manifest.load_code(copy, "tools", "gate_probe").BIG = 1 << 10
    probe = manifest.load_code(copy, "tools", "gate_probe_mimo")
    assert probe.main([TINY_CELL, "5", "long=60,100"], root=copy,
                      need_chip=False) == 0
    rows = [json.loads(x[5:]) for x in capsys.readouterr().out.splitlines()
            if x.startswith("GATE ")]
    assert [(r["variant"], r["prompt"]) for r in rows] == [
        ("as configured", [300, 290]), ("as configured", 60),
        ("as configured", 100), ("sinks zeroed", [300, 290]),
        ("sinks sign-flipped", [300, 290]),
        ("sinks on other heads", [300, 290]),
        ("values unscaled", [300, 290]),
        ("weights through int8", [300, 290]), ("weights through int8", 60),
        ("weights through int8", 100)]
    assert all(r["passes"] and r["rms"] < 1e-4 for r in rows[:3])
    assert not any(r["passes"] for r in rows[3:])
    # (n + 8 - 1 - 24) // 8 pages gone by the last step; never more than
    # ceil((24 + 16) / 8) + 1 held
    assert [r["pages_freed"] for r in rows[1:3]] == [5, 10]
    assert all(r["window_pages_max"] <= 6 for r in rows[1:3])
    assert (copy / f"chiprun_out/gate_probe/{TINY_CELL}.mimo.json").exists()
