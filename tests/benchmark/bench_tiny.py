"""A temporary copy of the benchmark with tiny cells added to it, by
adding files and entries only: the way a later PR adds a configuration, a
traffic mix or a per-layer metric.  The tests run these cells on the CPU
through ``benchmark.lib.harness.run_cell``; ``run.py`` itself refuses a
machine without the chip."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

_DS = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
       "bf16": {"enabled": True}, "gradient_clipping": 1.0,
       "steps_per_print": 1000000,
       "activation_checkpointing": {"remat_policy": "dots_flash_saveable"}}

CONFIGS = {
    "gpt2-tiny": {
        "source": "tests", "n_layer": 2, "n_embd": 128, "n_head": 4,
        "n_positions": 64, "vocab_size": 512, "layer_norm_epsilon": 1e-5,
        "reduced": [], "assumed": {}, "kind": "train", "reference": "gpt2",
        "registry": {"name": "gpt2-tiny", "overrides": {"max_seq_len": 64}},
        "expect": {"num_layers": 2, "hidden_size": 128},
        "ds_config": dict(_DS, train_micro_batch_size_per_gpu=2,
                          gradient_accumulation_steps=2,
                          zero_optimization={"stage": 1}),
        "mesh": {"data": 1}, "loss_tolerance": 0.02},
    "opt-tiny": {
        "source": "tests", "num_hidden_layers": 2, "hidden_size": 128,
        "num_attention_heads": 4, "ffn_dim": 512,
        "max_position_embeddings": 64, "vocab_size": 512,
        "reduced": [], "assumed": {}, "kind": "train", "reference": "opt",
        "registry": {"name": "opt-tiny", "overrides": {"max_seq_len": 64}},
        "expect": {"num_layers": 2, "activation": "relu"},
        "ds_config": dict(_DS, train_micro_batch_size_per_gpu=2,
                          gradient_accumulation_steps=1,
                          zero_optimization={"stage": 3}),
        "mesh": {"data": 4}, "loss_tolerance": 0.02},
    "mistral-tiny": {
        "source": "tests", "num_hidden_layers": 2, "hidden_size": 128,
        "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 32,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "vocab_size": 512,
        "reduced": [], "assumed": {}, "kind": "serve",
        "reference": "mistral",
        "registry": {"name": "mistral-tiny",
                     "overrides": {"param_dtype": "bfloat16"}},
        "expect": {"num_layers": 2, "kv_heads": 2, "sliding_window": 32},
        "engine_config": {
            "dtype": "bfloat16",
            "memory_config": {"num_blocks": 128, "block_size": 16},
            "max_context": 128,
            "state_manager": {"max_tracked_sequences": 8,
                              "max_ragged_batch_size": 32}},
        "server_config": {}, "logit_rms_tolerance": 0.05},
}

_SERVE = {"prompt_tokens": {"min": 8, "max": 60},
          "answer_tokens": {"min": 3, "max": 8},
          "answer_follows_prompt": False, "block": 4, "base_seed": 7,
          "drain_s": 60}
TRAFFIC = {
    "tiny_steps": {"driver": "train_steps", "seq_len": 64, "base_seed": 7,
                   "correct_rows": 4},
    "tiny_open": dict(_SERVE, driver="open_loop", rate_per_s=6.0),
    "tiny_closed": dict(_SERVE, driver="closed_loop", clients=2,
                        requests_per_s_ceiling=40.0,
                        answer_follows_prompt=True),
}
CELLS = [("gpt2-tiny", "tiny_steps", 1), ("opt-tiny", "tiny_steps", 4),
         ("mistral-tiny", "tiny_open", 1), ("mistral-tiny", "tiny_closed", 1)]

EXTRA_READER = '''\
def steps_counted(run, cell):
    """A per-layer metric a later PR might add: steps or requests run."""
    return float(run.attempted)
'''


def make_copy(dst: Path) -> Path:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` to ``dst`` and add the
    tiny configurations, mixes, cells and one new per-layer metric."""
    dst = Path(dst)
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = dst / "benchmark"
    man = json.loads((dst / "BENCHMARK.json").read_text())
    for name, cfg in CONFIGS.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        man["configs"].append({"name": name, "source": "tests",
                               "file": f"benchmark/configs/{name}.json",
                               "reduced": [], "why": "tiny preset"})
    for name, t in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(t))
    cells = []
    for config, traffic, chips in CELLS:
        cells.append(f"{config}.{traffic}")
        man["workloads"].append({"name": cells[-1], "config": config,
                                 "traffic": traffic, "chips": chips,
                                 "why": "tiny preset"})
    # a metric listed for some cells takes the tiny cells of the same
    # driver: the one edit to an entry that was there, as the schema asks
    mix_of = {w["name"]: w["traffic"] for w in man["workloads"]}

    def driver(cell):
        return json.loads((bench / "traffic" / f"{mix_of[cell]}.json")
                          .read_text())["driver"]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            drivers = {driver(w) for w in m["workloads"]}
            m["workloads"] = m["workloads"] + [c for c in cells
                                               if driver(c) in drivers]
    (bench / "readers" / "extra.py").write_text(EXTRA_READER)
    (bench / "layer_metrics" / "steps_counted.json").write_text(json.dumps(
        {"name": "steps_counted", "reader": "extra.steps_counted",
         "what": "steps or requests run"}))
    man["per_layer"].append({
        "name": "steps_counted", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train entry",
        "moves": "setup_s"})
    (dst / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return dst
