"""On-chip config sweep for bench.py tuning. Not part of the test suite.

Usage: python tools/bench_sweep.py '{"remat_policy": "none", "loss_tiles": 8}' ...
Each JSON arg is a variant of overrides; prints tokens/s per variant.
Override keys: batch, gas, seq, remat_policy, loss_tiles, scan_unroll,
zero_stage, model.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(ov: dict) -> float:
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.models import get_model_config

    topology._GLOBAL_TOPOLOGY = None
    batch_size = ov.get("batch", 8)
    gas = ov.get("gas", 8)
    seq = ov.get("seq", 1024)
    model_kw = {}
    if ov.get("loss_tiles"):
        model_kw["loss_tiles"] = ov["loss_tiles"]
    if ov.get("scan_unroll"):
        model_kw["scan_unroll"] = ov["scan_unroll"]
    model = get_model_config(ov.get("model", "gpt2-350m"), max_seq_len=seq,
                             **model_kw)
    config = {
        "train_micro_batch_size_per_gpu": batch_size,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": ov.get("zero_stage", 1)},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "activation_checkpointing": {
            "remat_policy": ov.get("remat_policy", "dots_saveable")},
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rows = batch_size * gas
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, size=(rows, seq + 1), dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].astype(np.int32)}
    for _ in range(3):
        loss = engine.train_batch(batch)
    float(np.asarray(loss))
    steps = 8
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    float(np.asarray(loss))
    dt = time.perf_counter() - t0
    tps = steps * rows * seq / dt
    return tps


def main():
    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    for arg in sys.argv[1:]:
        ov = json.loads(arg)
        try:
            tps = run_variant(ov)
            print(f"RESULT {json.dumps(ov)} -> {tps:,.1f} tok/s", flush=True)
        except Exception as e:
            print(f"RESULT {json.dumps(ov)} -> FAILED: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)


if __name__ == "__main__":
    main()
