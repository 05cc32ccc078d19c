"""Validate the autotuner's trial ordering on real TPU hardware.

The round-3 verdict flagged that autotuner trials had only ever executed
on the virtual CPU mesh, so the throughput ordering it optimizes was
never checked against the chip.  This tool runs a grid sweep
(micro-batch × ZeRO stage, gpt2-125m @ seq 512) with the SAME trial
machinery (crash-isolated subprocesses → deepspeed_tpu.autotuning.
trial_runner) on the live TPU backend, then reports:

* the measured throughput ranking,
* whether the model-based mode's predicted first choice (largest
  micro-batch, highest stage) is the measured winner or within 10%.

Writes ``AUTOTUNE_TPU.json`` at the repo root for the record.
Not part of the suite (needs the chip).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.utils.platform import device_facts_from_child

    # this process starts the isolated trials, which need the chip, so
    # it never initialises a JAX backend itself
    facts = device_facts_from_child()
    assert facts["platform"] == "tpu", "needs the TPU backend"
    model = get_model_config("gpt2-125m", max_seq_len=512)
    base = {
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "dots_flash_saveable"},
    }
    tuner = Autotuner(model, base, seq_len=512, mode="grid",
                      steps_per_trial=4, max_trials=12,
                      trial_timeout=420.0)
    best, results = tuner.tune(patience=100)

    rows = sorted((r for r in results), key=lambda r: -r.throughput)
    report = {"device": facts["device_kind"],
              "space": "grid micro_batch x zero_stage, gpt2-125m seq512",
              "results": [
                  {"cand": r.config,
                   "tokens_per_sec": round(r.throughput * 512, 1),
                   # failed trials carry inf — not valid strict JSON
                   "step_seconds": None if r.step_seconds == float("inf")
                   else round(r.step_seconds, 4),
                   "error": r.error}
                  for r in rows]}
    # model-based prediction = head of the model_based ordering
    pred = Autotuner(model, base, seq_len=512, mode="model_based",
                     max_trials=1)._space()
    report["model_based_first_choice"] = pred[0] if pred else None
    if rows and pred:
        measured_best = report["results"][0]["cand"]
        within = [r for r in report["results"]
                  if r["cand"] == pred[0] and r["tokens_per_sec"] >=
                  0.9 * report["results"][0]["tokens_per_sec"]]
        report["prediction_is_winner"] = measured_best == pred[0]
        report["prediction_within_10pct"] = bool(within)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "AUTOTUNE_TPU.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
