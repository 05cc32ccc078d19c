"""The ``nemotron-3-nano-ep2-l9`` configuration and its per-layer metrics:
the file holds the catalog row's config but for its four cuts and builds
the model it describes (the arithmetic of ``reduced_why`` from the
program's own shapes, nothing allocated), the cell's traffic as the issue
names it, the three readers on recorded spans and on another program's,
the engine's ``kernel_calls_per_step`` against the step program's jaxpr,
and the cell at the tiny preset end to end in a temporary copy."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from benchmark.lib import device, harness, manifest, traffic
from benchmark.lib.model import build_model
from benchmark.lib.run import Run

ROOT = Path(__file__).resolve().parents[2]
CONFIG = "nemotron-3-nano-ep2-l9"
CELL = CONFIG + ".reason_many_open"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUTS = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
TINY_CELL = "nemotron-h-tiny.tiny_many_open"
NEW = ["ssd_scan_roofline", "expert_rows_per_held_p50", "kv_cache_share_p50"]
FAMILY = ["queue_wait_p50_ms.open", "serve_step_ms_p50.open",
          "prefill_tokens_per_s.open", "compiles_in_window.open",
          "pallas_time_share.open", "device_idle_share.open",
          "loadgen_late_p95_ms", "ttft_p95_ms.open", "token_gap_p95_ms.open",
          "ssm_time_share.open", "state_slots_live_p50"]

# the published names the reference reads, at the registry's tiny sizes
TINY = {
    "source": "tests", "hidden_size": 64, "num_hidden_layers": 9,
    "hybrid_override_pattern": "MEMEM*EME", "vocab_size": 512,
    "layer_norm_epsilon": 1e-5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "use_conv_bias": True, "n_routed_experts": 8,
    "experts_held_first": 8, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "reduced": [], "assumed": {}, "kind": "serve", "reference": "nemotron_h",
    "registry": {"name": "nemotron-h-tiny",
                 "overrides": {"param_dtype": "bfloat16"}},
    "expect": {"num_layers": 9, "layer_kinds": "MEMEM*EME",
               "experts_held": 8, "n_routed_experts": 16, "ssm_layers": 4,
               "attn_layers": 1, "expert_layers": 4, "route_scale": 2.5},
    # float32 arithmetic on the bf16 weights: 1e-5 as configured
    "engine_config": {
        "dtype": "float32",
        "memory_config": {"num_blocks": 128, "block_size": 8},
        "max_context": 128,
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 16,
                          "min_context_blocks": 16}},
    "server_config": {}, "logit_rms_tolerance": 0.002}
TINY_TRAFFIC = {"driver": "open_loop", "rate_per_s": 6.0,
                "prompt_tokens": {"min": 8, "max": 60},
                "answer_tokens": {"min": 3, "max": 8},
                "answer_follows_prompt": False, "block": 4, "base_seed": 13,
                "drain_s": 60}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``bench_tiny``'s copy, and on top of it the tiny preset under an
    open-loop mix of its own, added the way this PR adds the real one: a
    file each, an entry each, and the cell's name at the end of the lists
    the real cell is on."""
    dst = bench_tiny.make_copy(tmp_path_factory.mktemp("bench_nemotron"))
    (dst / "benchmark/configs/nemotron-h-tiny.json").write_text(
        json.dumps(TINY))
    (dst / "benchmark/traffic/tiny_many_open.json").write_text(
        json.dumps(TINY_TRAFFIC))
    man = json.loads((dst / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "nemotron-h-tiny", "source": "tests",
                           "file": "benchmark/configs/nemotron-h-tiny.json",
                           "reduced": [], "why": "tiny preset"})
    man["workloads"].append({"name": TINY_CELL, "config": "nemotron-h-tiny",
                             "traffic": "tiny_many_open", "chips": 1,
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
    return json.loads((ROOT / f"benchmark/configs/{CONFIG}.json").read_text())


def test_the_file_builds_the_model_it_describes():
    cfg = _config()
    model = build_model(cfg)            # holds ``expect`` to the model
    hy, m = model.hybrid, model.ssm
    assert cfg["reduced"] == CUTS and set(cfg["published"]) == set(CUTS)
    assert cfg["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert hy.pattern == cfg["published"]["hybrid_override_pattern"]
    assert hy.pattern[:9] == cfg["hybrid_override_pattern"] \
        == model.layer_kinds
    assert (model.num_layers, model.vocab_size, hy.experts_held) == (
        cfg["num_hidden_layers"], cfg["vocab_size"],
        (cfg["experts_held_first"], cfg["n_routed_experts"]))
    assert (model.hidden_size, model.num_heads, model.kv_heads,
            model.dim_per_head, model.layernorm_eps, model.max_seq_len) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["layer_norm_epsilon"], cfg["max_position_embeddings"])
    assert (m.num_heads, m.head_dim, m.state_size, m.n_groups, m.conv_kernel,
            m.chunk_size, m.conv_bias) == (
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
        cfg["n_groups"], cfg["conv_kernel"], cfg["chunk_size"],
        cfg["use_conv_bias"])
    # heads x head dim, not expand x hidden
    assert m.d_ssm == 4096 != cfg["expand"] * cfg["hidden_size"]
    assert (hy.n_routed_experts, hy.num_experts_per_tok,
            hy.moe_intermediate_size, hy.shared_intermediate_size,
            hy.route_scale) == (
        cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"],
        cfg["routed_scaling_factor"])
    assert not model.use_rope and not model.tie_embeddings
    assert (cfg["n_group"], cfg["topk_group"], cfg["norm_topk_prob"],
            cfg["mlp_hidden_act"], cfg["residual_in_fp32"]) == (
        1, 1, True, "relu2", False)
    assert set(cfg["expect"]) >= {
        "hidden_size", "num_heads", "kv_heads", "dim_per_head", "ssm_heads",
        "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_conv",
        "n_routed_experts", "experts_held", "experts_per_tok", "expert_width",
        "shared_width", "route_scale", "vocab_size", "layer_kinds"}
    assert set(cfg["assumed"]) >= {
        "A_log", "dt_bias", "D", "conv1d", "mamba_norm_weight", "d_inner",
        "state_dtype", "rotary", "routing", "initialisation"}
    assert "two" in cfg["stands_for"].lower() and cfg["kind"] == "serve"
    # the arithmetic of reduced_why, from the program's own shapes
    from deepspeed_tpu.models import transformer as tf_model

    shapes = jax.eval_shape(lambda k: tf_model.init_params(model, k),
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    layers = shapes["layers"]
    assert count(layers["ssm"]) // 4 == 38_742_208 == (
        2688 * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * 2688)
    assert count(layers["attn"]) == 23_396_352 == (
        2 * 2688 * 4096 + 2 * 2688 * 256)
    expert = 2 * 2688 * 1856
    assert count(layers["moe"]) // 4 == 658_882_688 == (
        64 * expert + 2 * 2688 * 3712 + 2688 * 128 + 128)
    assert layers["moe"]["wu"].shape == layers["moe"]["wo"].shape == (
        4, 64, 1856, 2688)
    assert count(shapes["embed"]) + count(shapes["lm_head"]) \
        == 2 * 65536 * 2688
    total = count(shapes)
    assert total == 3_166_244_352
    for said in ("3,166,244,352", "5.90 GiB", "38.74 M", "23.40 M",
                 "658.9 M", "352.3 M", "8.54 MB", "9.60 GB"):
        assert said in cfg["reduced_why"], said
    assert round(total * 2 / 2 ** 30, 2) == 5.90
    # the engine: slots bound admission, the pool holds every sequence
    eng = cfg["engine_config"]
    mem, state = eng["memory_config"], eng["state_manager"]
    bs = mem["block_size"]
    a_token = 2 * model.kv_heads * model.dim_per_head * 2 * model.attn_layers
    a_slot = model.ssm_layers * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert a_token == 1024 and a_slot == 8_536_064
    assert 192 <= state["max_tracked_sequences"] == 256
    assert (mem["num_blocks"] - 1) * bs \
        == state["max_tracked_sequences"] * eng["max_context"]
    # ONE block-table width: a step program a token bucket and no more
    assert state["min_context_blocks"] * bs == eng["max_context"] == 4096
    held = total * 2 + 257 * a_slot + mem["num_blocks"] * bs * a_token
    assert 0.25 < held / (15.75 * 2 ** 30) < 0.6
    assert cfg["server_config"] == {} \
        and cfg["attention_impl"] == "paged_pallas"
    for key in ("engine_config_why", "logit_rms_tolerance_why"):
        assert len(cfg[key]) > 200, key


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_config_but_for_its_cuts():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "-") != v]
    assert sorted(differs) == sorted(CUTS) == sorted(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in CUTS}
    assert row["config"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    entry = next(c for c in manifest.load_manifest(ROOT)["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == CUTS and entry["source"] == cfg["source"]


def test_the_cells_traffic_is_what_the_issue_names():
    cell = manifest.load_cell(ROOT, CELL)
    t = cell.traffic
    assert cell.chips == 1 and t["driver"] == "open_loop"
    assert t["prompt_tokens"] == {"min": 128, "max": 2048}
    assert t["answer_tokens"] == {"min": 512, "max": 2048}
    assert (t["answer_follows_prompt"], t["block"], t["drain_s"],
            t["base_seed"]) == (False, 8, 60, 20261003)
    others = {json.loads(p.read_text()).get("base_seed")
              for p in (ROOT / "benchmark/traffic").glob("*.json")
              if p.stem != "reason_many_open"}
    assert t["base_seed"] not in others
    # every request fits a sequence's pages and the pool all of them
    limit = cell.config["engine_config"]["max_context"]
    assert traffic.max_context(t) == limit
    plan = traffic.serve_plan(t, 2 ** 31 + 5, 51.0, 65536)
    assert len(plan.requests) == round(t["rate_per_s"] * 51) >= 100
    assert all(len(r.prompt) + r.max_new_tokens <= limit
               and max(r.prompt) < 65536 for r in plan.requests)
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_mean_ms", "token_gap_mean_ms", "setup_s"}
    assert set(cell.readers) == {*FAMILY, *NEW}
    by = {m["name"]: m for m in cell.per_layer}
    assert [(n, by[n]["layer"], by[n]["moves"], by[n]["source"],
             by[n]["unit"], by[n]["workloads"]) for n in NEW] == [
        ("ssd_scan_roofline", "ssm kernels", "token_gap_mean_ms",
         "device_trace", "%", [CELL]),
        ("expert_rows_per_held_p50", "experts", "token_gap_mean_ms",
         "program_counter", "rows", [CELL]),
        ("kv_cache_share_p50", "paged cache", "ttft_mean_ms",
         "program_counter", "ratio", [CELL])]
    # (by place, not "the last": a later PR appends behind them)
    man = manifest.load_manifest(ROOT)
    assert [w["name"] for w in man["workloads"]].index(CELL) == 8
    assert sum(w["chips"] == 4 for w in man["workloads"][:9]) == 1
    names = [m["name"] for m in man["per_layer"]]
    assert names[names.index(NEW[0]):][:3] == NEW
    assert CELL not in next(m for m in man["per_layer"]
                            if m["name"] == "ssd_roofline")["workloads"]
    assert len(man["workloads"][-1]["why"]) <= 200


ALLOC = {"ssm_layers": 4, "attn_layers": 1, "expert_layers": 4,
         "slot_bytes": 8_536_064, "state_pool_bytes": 2_194_800_640,
         "kv_pool_bytes": 1_073_774_592, "page_bytes": 32768,
         "kernel_calls_per_step": 6, "ssm_bytes": 1, "conv_bytes": 1,
         "slots": 257, "ssm_impl": "ssd_pallas"}


def _recorded_run(model, traced=True):
    """Spans as a traced run records them: the allocation, five steps in
    the window around the traced stretch, one outside the window."""
    lo, hi = 1_000_000.0, 11_000_000.0
    steps = [  # ssm_rows, live, state_bytes, kv_pages_held, expert_rows
        (100, 90, 90 * 2 * 8_388_608, 900, 300.0),
        (120, 100, 100 * 2 * 8_388_608, 1000, 360.0),
        (128, 110, 110 * 2 * 8_388_608, 1200, 384.0),
        (400, 120, 120 * 2 * 8_388_608, 1300, 1200.0),
        (130, 125, 125 * 2 * 8_388_608, 1400, 390.0)]
    spans = [{"ph": "X", "name": "v2.state_alloc", "ts": 5.0, "dur": 9.0,
              "args": dict(ALLOC)}]
    spans += [{"ph": "X", "name": "v2.schedule", "ts": lo + 3e6 + i * 1e5,
               "dur": 40.0,
               "args": {"seqs": live, "tokens": rows, "ssm_runs": live,
                        "ssm_rows": rows, "state_slots_live": live,
                        "state_bytes": sb, "kv_rows": 9, "qk_pairs": 9,
                        "append_pages": live, "kv_pages_held": pages,
                        "expert_rows": er}}
              for i, (rows, live, sb, pages, er) in enumerate(steps)]
    spans.append({"ph": "X", "name": "v2.schedule", "ts": hi + 9.0,
                  "dur": 1.0, "args": dict(spans[-1]["args"],
                                           expert_rows=64000.0)})
    spans.append({"ph": "X", "name": "v2.schedule", "ts": lo + 5.0,
                  "dur": 1.0, "args": {"seqs": 0, "tokens": 0}})

    class Trace:
        """What ``lib.trace.Reduction`` gives a reader: 12 Pallas calls
        (two steps of six), 10 ms of them, 1 ms the attention's."""
        mosaic_calls, mosaic_s, busy_s = 12.0, 0.010, 0.1
        top_ops = [("fusion.1", 0.05), ("ssd_ragged.18 pallas", 0.004),
                   ("paged_qblock.1 pallas", 0.0008),
                   ("kv_append.1 pallas", 0.0002)]
    return Run(correct=True, attempted=1, failed=0, end_to_end={},
               setup_s=1.0, spans=spans, trace=Trace() if traced else None,
               counters={"window_mono_us": (lo, hi), "window_s": 10.0,
                         "model": model, "device_kind": "TPU v5 lite"})


def test_the_three_readers_on_recorded_spans_and_on_another_program():
    from benchmark.lib import ssm_cost
    from benchmark.lib.peaks import peaks_for

    cell = manifest.load_cell(ROOT, CELL)
    model = build_model(cell.config)
    run = _recorded_run(model)
    # 12 calls / 6 a step (the program's count) = 2 steps; the least work
    # of two consecutive steps is the first pair's; the kernel's time is
    # all Pallas time less the two other kernels the table names
    fl, by = ssm_cost.ssd_cost(220, 190 * 2 * 8_388_608, 4, 64, 64, 128, 8)
    least, bound = ssm_cost.least_time(fl, by, peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert cell.readers["ssd_scan_roofline"](run, cell) == pytest.approx(
        100 * least / (0.010 - 0.0008 - 0.0002))
    assert cell.readers["expert_rows_per_held_p50"](run, cell) \
        == pytest.approx(384.0 / 64)
    # (pages held, slots live) of the five steps: the median SHARE
    shares = sorted(p * 32768 / (p * 32768 + n * 8_536_064) for p, n in (
        (900, 90), (1000, 100), (1200, 110), (1300, 120), (1400, 125)))
    assert cell.readers["kv_cache_share_p50"](run, cell) \
        == pytest.approx(shares[2])
    # the count is the program's: another figure, another number of steps
    run.spans[0]["args"]["kernel_calls_per_step"] = 12
    one = cell.readers["ssd_scan_roofline"](run, cell)
    fl, by = ssm_cost.ssd_cost(100, 90 * 2 * 8_388_608, 4, 64, 64, 128, 8)
    assert one == pytest.approx(
        100 * ssm_cost.least_time(fl, by, peaks_for("TPU v5 lite"))[0]
        / 0.009)
    # no trace (a CPU run), no kernel in it, a program that makes no
    # Pallas call: no roofline, and nothing raised
    assert cell.readers["ssd_scan_roofline"](
        _recorded_run(model, traced=False), cell) is None
    run.spans[0]["args"]["kernel_calls_per_step"] = 0
    assert cell.readers["ssd_scan_roofline"](run, cell) is None
    # another model's program (a mixer beside attention; the parent
    # commit's): no such argument in a span, and nothing is raised
    for e in run.spans:
        e["args"] = {k: v for k, v in e["args"].items() if k in (
            "seqs", "tokens", "ssm_runs", "ssm_rows", "state_slots_live",
            "state_bytes", "ssm_bytes", "conv_bytes", "slots", "ssm_impl")}
    for name in NEW:
        assert cell.readers[name](run, cell) is None
    run.spans = []
    for name in NEW:
        assert cell.readers[name](run, cell) is None


def _pallas_calls(jaxpr, times=1):
    """``pallas_call`` equations a jaxpr EXECUTES: one inside a scan
    counts once a trip."""
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


@pytest.mark.parametrize("modules,kv_dtype,want", [
    ({"attention": "paged_pallas", "ssm": "ssd_pallas"}, None, 6),
    ({"attention": "paged_pallas", "ssm": "ssd_xla"}, "int8", 1),
    ({"attention": "paged_xla", "ssm": "ssd_pallas"}, None, 4)])
def test_kernel_calls_per_step_is_the_step_programs_own(modules, kv_dtype,
                                                        want):
    """The figure the engine puts in ``v2.state_alloc`` equals the
    ``pallas_call``s the step program's jaxpr executes (four scans, two of
    them in the layer walk's one ``lax.scan``; an append and a read), for
    every choice of kernels."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_model_config

    model = get_model_config("nemotron-h-tiny", head_dim=128)
    memory = {"num_blocks": 16, "block_size": 8}
    if kv_dtype:
        memory["kv_dtype"] = kv_dtype
    eng = InferenceEngineV2(model, {
        "dtype": "float32", "modules": modules, "memory_config": memory,
        "max_context": 64,
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_batch_size": 16}})
    said = eng._state_alloc["kernel_calls_per_step"]
    fn, args = eng.audit_step_args("decode")
    assert said == want == _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)


def test_a_tiny_cell_of_slots_pages_and_experts_end_to_end(copy, plain_jit):
    """A traced run (an untraced one differs in nothing this model adds:
    ``test_benchmark_cells.py`` runs the other cells both ways)."""
    out = harness.run_cell(copy, TINY_CELL, 2 ** 31 + 7, 1.5, True,
                           time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    # no device plane here, so the readers of the trace find nothing and
    # leave their metrics out; the program's spans are read on the CPU too
    assert set(out["metrics"]) == {
        "queue_wait_p50_ms.open", "serve_step_ms_p50.open",
        "prefill_tokens_per_s.open", "compiles_in_window.open",
        "ttft_p95_ms.open", "token_gap_p95_ms.open", "loadgen_late_p95_ms",
        "state_slots_live_p50", "expert_rows_per_held_p50",
        "kv_cache_share_p50", "steps_counted"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["state_slots_live_p50"] >= 1
    # rows x 3 of 16 experts, 8 of them held, over 8: rows x 3 / 16
    assert 0 < m["expert_rows_per_held_p50"] <= 16 * 3 / 16
    # a page of 8 rows is 8 x 2 x 2 x 16 x 4 B = 4 KB against a slot of
    # 4 x (8 x 8 x 16 x 4 B + 3 x 128 x 4 B) = 22 KB
    assert 0 < m["kv_cache_share_p50"] < 1
    # the warm-up's steps ran the programs the serve loop used
    assert m["compiles_in_window.open"] == 0
