#!/usr/bin/env python3
"""Does a serving cell's ``correct`` gate refuse a lower precision than
the configuration states?  One engine as configured, then the same check
with the weights round-tripped through int8 (the reference keeps the
bf16 originals), then a second engine with an int8 KV cache.  (A round
trip through fp8 by ``astype`` is not here: XLA elides the pair of
converts on the chip, and the variant read exactly as configured.)

    chiprun -- python3 benchmark/tools/gate_probe.py <workload> <seed>...

Prints one row per variant and seed (rms and largest difference as
``lib.serve_driver.logit_errors`` defines them, and whether the
configuration's tolerance passes it) and writes them to
``chiprun_out/gate_probe/<workload>.json``.  The tolerance in the
configuration file is set from this table by hand.
"""

from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

BIG = 1 << 20          # leaves with more elements are matmul weights


def _int8(w):
    """Symmetric int8 with one scale per output column, and back."""
    import jax.numpy as jnp

    x = w.astype(jnp.float32)
    s = jnp.abs(x).max(axis=-2, keepdims=True) / 127.0
    return (jnp.clip(jnp.rint(x / s), -127, 127) * s).astype(w.dtype)


def main(argv, root: Path = ROOT, need_chip: bool = True) -> int:
    workload, seeds = argv[0], [int(s) for s in argv[1:]]
    import jax

    from benchmark.lib import device, serve_driver
    from benchmark.lib.manifest import load_cell
    from benchmark.lib.model import build_model
    from benchmark.lib.run import seed32
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    cell = load_cell(root, workload)
    if need_chip:
        device.require_chips(cell.chips)
    device.setup_compile_cache()
    cfg = cell.config
    tol = float(cfg["logit_rms_tolerance"])
    model = build_model(cfg)
    rows = []

    def probe(variant, eng, reference_params=None):
        for seed in seeds:
            e = serve_driver.logit_errors(cell, eng, model, seed,
                                          reference_params)
            rows.append(dict(e, variant=variant, seed=seed,
                             passes=bool(e["rms"] <= tol)))
            print("GATE", json.dumps(rows[-1]), flush=True)

    def engine(engine_config):
        eng = InferenceEngineV2(model, engine_config, seed=seed32(seeds[0]))
        jax.block_until_ready(eng.params)
        return eng

    eng = engine(dict(cfg["engine_config"]))
    probe("as configured", eng)
    original = jax.device_get(eng.params)        # the reference's weights
    placed = jax.tree.map(lambda a: a.sharding, eng.params)
    eng.params = None                            # room for the copy
    round_trip = jax.jit(_int8, donate_argnums=0)
    eng.params = jax.tree.map(
        lambda w: round_trip(w) if w.size > BIG else w,
        jax.device_put(original, placed))
    probe("weights through int8", eng, original)
    del eng, original
    gc.collect()
    quantised = dict(cfg["engine_config"])
    quantised["memory_config"] = dict(quantised["memory_config"],
                                      kv_dtype="int8")
    probe("int8 KV cache", engine(quantised))

    out = root / "chiprun_out" / "gate_probe"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
