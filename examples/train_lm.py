#!/usr/bin/env python3
"""Minimal end-to-end training example (the DeepSpeedExamples analog).

Run single-host::

    python examples/train_lm.py --model llama-tiny \
        --deepspeed_config examples/ds_config_zero3_bf16.json --steps 50

or through the launcher (multi-process/multi-host)::

    bin/dstpu --num_nodes 1 examples/train_lm.py --deepspeed_config ...
"""

import argparse
import sys

import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.models import get_model_config
from deepspeed_tpu.utils.platform import setup_compile_cache


def synthetic_batches(vocab, rows, seq, steps, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        ids = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
        yield {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2-tiny")
    ap.add_argument("--deepspeed_config", "--config", dest="config",
                    default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--save_dir", default=None)
    ap.add_argument("--local_rank", type=int, default=-1)  # launcher parity
    args = ap.parse_args(argv)

    setup_compile_cache()
    model = get_model_config(args.model)
    config = args.config or {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rows = engine.train_batch_size_value
    for step, batch in enumerate(
            synthetic_batches(model.vocab_size, rows, args.seq, args.steps)):
        loss = engine.train_batch(batch)
        if step % 10 == 0:
            print(f"step {step}: loss {float(np.asarray(loss)):.4f}")
    if args.save_dir:
        engine.save_checkpoint(args.save_dir)
    print(f"done: final loss {float(np.asarray(loss)):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
