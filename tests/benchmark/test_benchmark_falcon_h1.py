"""The ``falcon-h1-34b-l6`` configuration and the state-space mixer's
per-layer metrics: the file builds the model it describes, the scan's
cost against a count by hand, and the three readers on a synthetic run."""

import json
from pathlib import Path

import pytest

from benchmark.lib import manifest, ssm_cost
from benchmark.lib.model import build_model
from benchmark.lib.peaks import PEAKS
from benchmark.lib.run import Run
from benchmark.lib.trace import Reduction

ROOT = Path(__file__).resolve().parents[2]
CELL = "falcon-h1-34b-l6.chat_short_open"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_file_builds_the_model_it_describes():
    cfg = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-l6.json")
                     .read_text())
    model = build_model(cfg)
    assert model.num_layers == cfg["num_hidden_layers"] == 6
    assert cfg["published"] == {"num_hidden_layers": 72}
    assert cfg["reduced"] == ["num_hidden_layers"]
    s = model.ssm
    assert (s.d_ssm, s.num_heads, s.head_dim, s.state_size, s.n_groups,
            s.conv_kernel, s.chunk_size) == (
        cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
        cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"],
        cfg["mamba_chunk_size"])
    for key, got in [
            ("embedding_multiplier", s.embedding_multiplier),
            ("lm_head_multiplier", s.lm_head_multiplier),
            ("attention_in_multiplier", s.attention_in_multiplier),
            ("attention_out_multiplier", s.attention_out_multiplier),
            ("key_multiplier", s.key_multiplier),
            ("ssm_in_multiplier", s.ssm_in_multiplier),
            ("ssm_out_multiplier", s.ssm_out_multiplier),
            ("ssm_multipliers", list(s.ssm_multipliers)),
            ("mlp_multipliers", list(s.mlp_multipliers)),
            ("rope_theta", model.rope_theta),
            ("rms_norm_eps", model.layernorm_eps),
            ("vocab_size", model.vocab_size),
            ("hidden_size", model.hidden_size),
            ("intermediate_size", model.intermediate_size),
            ("max_position_embeddings", model.max_seq_len)]:
        assert cfg[key] == got, key
    # the engine's sizes: the arithmetic ISSUE 29 gives
    eng = cfg["engine_config"]
    rows = eng["memory_config"]["num_blocks"] * eng["memory_config"][
        "block_size"]
    assert rows * 6 * 2 * model.kv_heads * model.dim_per_head * 2 \
        == 816 * 2 ** 20
    slots = eng["state_manager"]["max_tracked_sequences"]
    assert slots * 6 * s.num_heads * s.head_dim * s.state_size * 4 \
        == 1536 * 2 ** 20


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_config_but_for_depth():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if '"Falcon-H1-34B-Instruct"' in line)
    cfg = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-l6.json")
                     .read_text())
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "-") != v]
    assert differs == ["num_hidden_layers"] == cfg["reduced"]


def test_ssd_cost_against_a_hand_count():
    # one layer, 3 rows, 2 heads of 4, state 8, 1 group; 1000 bytes of state
    fl, by = ssm_cost.ssd_cost(3, 1000, layers=1, heads=2, head_dim=4,
                               state=8, groups=1)
    assert fl == 3 * 2 * (2 * 4 * 8 + 2 * 4 * 8)       # update + output
    # a row: x and y 2*4 each, B and C 8 each, two bytes; dt 2 floats
    assert by == 1000 + 3 * ((8 + 8 + 8 + 8) * 2 + 2 * 4)
    # published widths: a decode row of one layer moves 8 MiB of state
    # and needs 4.2 MFLOP
    fl, by = ssm_cost.ssd_cost(1, 2 * 32 * 128 * 256 * 4, 1, 32, 128, 256, 2)
    assert fl == 4 * 128 * 256 * 32 and 8 * 2 ** 20 < by < 8.1 * 2 ** 20
    peaks = PEAKS["TPU v5 lite"]
    s, bound = ssm_cost.least_time(fl, by, peaks)
    assert bound == "memory" and s == pytest.approx(by / 819e9)
    assert ssm_cost.least_time(1e15, 1.0, peaks)[1] == "compute"


def _synthetic_run(n_steps=40, traced_steps=10, layers=6):
    """A window of 20 s with a step every 0.25 s around its middle, each
    one run more than the last, and a trace that caught ``traced_steps``
    of them."""
    lo = 1_000_000.0
    hi = lo + 20e6
    mid = lo + 7.5e6                  # (20 - 5) / 2 into the window
    slot = 32 * 128 * 256 * 4 * layers
    spans = []
    for i in range(n_steps):
        spans.append({"ph": "X", "name": "v2.schedule",
                      "ts": mid + i * 0.25e6, "dur": 50.0,
                      "args": {"ssm_runs": 10 + i, "ssm_rows": 10 + i,
                               "state_slots_live": 10 + i,
                               "state_bytes": 2 * slot * (10 + i)}})
    spans.append({"ph": "X", "name": "v2.schedule", "ts": mid, "dur": 1.0,
                  "args": {"seqs": 0, "tokens": 0}})      # an empty one
    model = build_model(json.loads(
        (ROOT / "benchmark/configs/falcon-h1-34b-l6.json").read_text()))
    trace = Reduction(
        chips=1, window_s=5.0, busy_s=4.0, mosaic_s=1.5,
        mosaic_calls=float(2 * layers * traced_steps), collective_s=0.0,
        exposed_collective_s=0.0,
        top_ops=[["fusion.1 fusion", 2.0], ["ssd_ragged.4 pallas", 0.9],
                 ["ssd_ragged.7 pallas", 0.1], ["paged_qblock.4 pallas", 0.5]],
        idle_gaps=[])
    return Run(correct=True, attempted=1, failed=0, end_to_end={},
               setup_s=1.0, spans=spans, trace=trace,
               counters={"window_mono_us": (lo, hi), "model": model,
                         "device_kind": "TPU v5 lite"})


def test_the_three_readers_on_a_synthetic_run(capsys):
    cell = manifest.load_cell(ROOT, CELL)
    assert {"ssd_roofline", "ssm_time_share.open",
            "state_slots_live_p50"} <= set(cell.readers)
    run = _synthetic_run()
    # all Pallas time (1.5 s) less the paged kernel's 0.5 s by name
    assert cell.readers["ssm_time_share.open"](run, cell) \
        == pytest.approx(100 * 1.0 / 4.0)
    run.trace.mosaic_s = 1.7           # 0.2 s more under numbers not shown
    assert cell.readers["ssm_time_share.open"](run, cell) \
        == pytest.approx(100 * 1.2 / 4.0)
    run.trace.mosaic_s = 1.5
    assert cell.readers["state_slots_live_p50"](run, cell) \
        == pytest.approx(29)     # the lower of the two middle values
    # the least work of 10 consecutive steps is that of the first ten:
    # runs 10..19, 145 in all, each moving two slots' bytes of all layers
    got = cell.readers["ssd_roofline"](run, cell)
    fl, by = ssm_cost.ssd_cost(145, 2 * 145 * 32 * 128 * 256 * 4 * 6, 6, 32,
                               128, 256, 2)
    assert got == pytest.approx(100 * (by / 819e9) / 1.0)
    assert 0 < got < 100
    assert "bound by memory" in capsys.readouterr().out


def test_the_readers_find_nothing_on_a_program_without_a_mixer():
    cell = manifest.load_cell(ROOT, CELL)
    run = _synthetic_run()
    run.trace.top_ops = [["fusion.1 fusion", 2.0],
                         ["paged_qblock.4 pallas", 0.5]]
    run.spans = [dict(e, args={"seqs": 1, "tokens": 1}) for e in run.spans]
    for name in ("ssd_roofline", "ssm_time_share.open",
                 "state_slots_live_p50"):
        assert cell.readers[name](run, cell) is None
    run.trace = None                      # a CPU rehearsal
    assert cell.readers["ssd_roofline"](run, cell) is None
    assert cell.readers["ssm_time_share.open"](run, cell) is None


# -- the cell kind end to end, tiny, on the CPU ------------------------------
TINY = {
    "source": "tests", "model_type": "falcon_h1", "num_hidden_layers": 2,
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "mamba_d_ssm": 128,
    "mamba_expand": 2, "mamba_n_heads": 4, "mamba_d_head": 32,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "embedding_multiplier": 2.5,
    "lm_head_multiplier": 0.5, "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.7, "key_multiplier": 0.6,
    "ssm_in_multiplier": 0.8, "ssm_out_multiplier": 1.3,
    "ssm_multipliers": [0.7, 1.2, 0.9, 1.1, 0.8],
    "mlp_multipliers": [1.4, 0.75],
    "reduced": [], "assumed": {}, "kind": "serve", "reference": "falcon_h1",
    "registry": {"name": "falcon-h1-tiny",
                 "overrides": {"param_dtype": "bfloat16"}},
    "expect": {"num_layers": 2, "ssm_heads": 4, "ssm_state": 16},
    "engine_config": {
        "dtype": "bfloat16",
        "memory_config": {"num_blocks": 128, "block_size": 16},
        "max_context": 128,
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 32}},
    "server_config": {}, "logit_rms_tolerance": 0.05}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``bench_tiny``'s copy, and on top of it the tiny Falcon-H1 preset
    under the open-loop mix, added the way this PR adds the real one."""
    import bench_tiny

    dst = bench_tiny.make_copy(tmp_path_factory.mktemp("bench_falcon"))
    (dst / "benchmark/configs/falcon-h1-tiny.json").write_text(
        json.dumps(TINY))
    man = json.loads((dst / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "falcon-h1-tiny", "source": "tests",
                           "file": "benchmark/configs/falcon-h1-tiny.json",
                           "reduced": [], "why": "tiny preset"})
    cell = "falcon-h1-tiny.tiny_open"
    man["workloads"].append({"name": cell, "config": "falcon-h1-tiny",
                             "traffic": "tiny_open", "chips": 1,
                             "why": "tiny preset"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(cell)
    (dst / "BENCHMARK.json").write_text(json.dumps(man))
    return dst


@pytest.fixture
def plain_jit(monkeypatch):
    import jax

    from benchmark.lib import device

    monkeypatch.setattr(device, "setup_compile_cache", lambda: "(off)")
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", was)


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_a_tiny_mixer_cell_end_to_end(copy, traced, plain_jit):
    import time

    from benchmark.lib import harness

    out = harness.run_cell(copy, "falcon-h1-tiny.tiny_open", 2 ** 31 + 11,
                           1.5, traced, time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    if not traced:
        assert set(out["metrics"]) == {"ttft_mean_ms", "token_gap_mean_ms",
                                       "setup_s"}
        return
    # no device plane on the CPU: the two trace readers find nothing
    assert "state_slots_live_p50" in out["metrics"]
    assert out["metrics"]["state_slots_live_p50"]["value"] >= 1
    assert not {"ssd_roofline", "ssm_time_share.open"} & set(out["metrics"])
    assert out["metrics"]["compiles_in_window.open"]["value"] == 0


def test_a_bf16_state_and_int8_mixer_weights_read_larger(copy, plain_jit,
                                                         capsys):
    """``tools/gate_probe_ssm.py`` at the tiny preset: each lower precision
    reads a larger error than the engine as configured.  What the real
    tolerance refuses is read on the chip."""
    probe = manifest.load_code(copy, "tools", "gate_probe_ssm")
    gate = manifest.load_code(copy, "tools", "gate_probe")
    assert probe.main(["falcon-h1-tiny.tiny_open", "5", str(2 ** 31 + 9)],
                      root=copy, need_chip=False) == 0
    rows = [json.loads(line[5:]) for line in capsys.readouterr().out
            .splitlines() if line.startswith("GATE ")]
    by = {}
    for r in rows:
        by.setdefault(r["variant"], []).append(r["rms"])
    assert set(by) == {"as configured", "recurrent state through bf16",
                       "mixer weights through int8"}
    assert all(len(v) == 2 for v in by.values()) and gate.BIG
    assert all(r["passes"] for r in rows if r["variant"] == "as configured")
    base = by["as configured"]
    for variant in ("recurrent state through bf16",
                    "mixer weights through int8"):
        assert all(v > b for v, b in zip(by[variant], base)), (variant, by)
