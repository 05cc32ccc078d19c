#!/usr/bin/env python3
"""Does the ``correct`` gate of a cell whose model has a state-space mixer
refuse a lower precision in the mixer than the configuration states?
``gate_probe.py`` asks it of the weights as a whole and of the KV cache;
this asks it of the two things only such a model has.  One engine:

- ``as configured``;
- ``recurrent state through bf16``: after every step the float32
  recurrent state is rounded to bf16 and back, by two programs of their
  own (inside one program XLA elides the pair), so that what a slot hands
  from one step to the next is what a bf16 slot would hold;
- ``mixer weights through int8``: the mixer's two projections
  round-tripped through int8, everything else as it was; the reference
  keeps the originals.

    chiprun -- python3 benchmark/tools/gate_probe_ssm.py <workload> <seed>...

Prints one row per variant and seed and writes them to
``chiprun_out/gate_probe/<workload>.ssm.json``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv, root: Path = ROOT, need_chip: bool = True) -> int:
    workload, seeds = argv[0], [int(s) for s in argv[1:]]
    import jax
    import jax.numpy as jnp

    from benchmark.lib import device, serve_driver
    from benchmark.lib.manifest import load_cell, load_code
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
    if model.ssm is None:
        raise SystemExit(f"{workload}: the model has no state-space mixer")
    int8 = load_code(root, "tools", "gate_probe")._int8
    rows = []

    def probe(variant, eng, reference_params=None):
        for seed in seeds:
            e = serve_driver.logit_errors(cell, eng, model, seed,
                                          reference_params)
            rows.append(dict(e, variant=variant, seed=seed,
                             passes=bool(e["rms"] <= tol)))
            print("GATE", json.dumps(rows[-1]), flush=True)

    eng = InferenceEngineV2(model, dict(cfg["engine_config"]),
                            seed=seed32(seeds[0]))
    jax.block_until_ready(eng.params)
    probe("as configured", eng)

    down = jax.jit(lambda s: s.astype(jnp.bfloat16), donate_argnums=0)
    up = jax.jit(lambda s: s.astype(jnp.float32))
    carried = eng._carried

    def through_bf16(out):
        out = carried(out)
        eng.state["ssm"] = up(down(eng.state["ssm"]))
        return out
    eng._carried = through_bf16
    probe("recurrent state through bf16", eng)
    eng._carried = carried

    ssm = eng.params["layers"]["ssm"]
    original = {k: jax.device_get(ssm[k]) for k in ("in_proj", "out_proj")}
    round_trip = jax.jit(int8, donate_argnums=0)
    for k in original:
        ssm[k] = round_trip(ssm[k])
    reference_params = dict(eng.params, layers=dict(
        eng.params["layers"], ssm=dict(ssm, **{
            k: jax.device_put(v, ssm[k].sharding)
            for k, v in original.items()})))
    probe("mixer weights through int8", eng, reference_params)

    out = root / "chiprun_out" / "gate_probe"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.ssm.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
