"""Autotuner trial body — shared by in-process and subprocess execution.

``run_timed_trial`` is THE definition of a trial (engine build → one
warmup/compile step → timed steps → samples/sec); the subprocess path
(``python -m deepspeed_tpu.autotuning.trial_runner payload.pkl``) and
``Autotuner._run_trial_inprocess`` both call it, so isolated and
in-process scores stay comparable by construction.
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import time

RESULT_PREFIX = "DSTPU_TRIAL "


def run_timed_trial(model_cfg, config, seq_len: int, steps: int) -> dict:
    """→ {"step_seconds", "throughput"} for one candidate config."""
    import numpy as np

    import deepspeed_tpu as ds

    engine, _, _, _ = ds.initialize(model=model_cfg, config=config)
    rng = np.random.default_rng(0)
    rows = engine.train_batch_size_value
    ids = rng.integers(0, model_cfg.vocab_size, size=(rows, seq_len + 1),
                       dtype=np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    loss = engine.train_batch(batch)  # compile step (excluded from timing)
    float(np.asarray(loss))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    float(np.asarray(loss))  # sync
    dt = (time.perf_counter() - t0) / steps
    return {"step_seconds": dt, "throughput": rows / dt}


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    with open(argv[0], "rb") as f:
        p = pickle.load(f)
    r = run_timed_trial(p["model_cfg"], p["config"], p["seq_len"], p["steps"])
    print(RESULT_PREFIX + json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
