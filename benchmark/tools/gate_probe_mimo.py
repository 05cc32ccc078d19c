#!/usr/bin/env python3
"""What does the ``correct`` gate of a cell whose window layers carry a
learned sink in the softmax and scaled values see, and which lower
precision than the configuration states does it refuse?  The gate's own
prompts (300 and 290 tokens) lie PAST a window of 128, so the gate itself
sees the window's mask and freed pages; this probe adds the long prompts
of ``gate_probe_window.py`` (its ``long_prompt_errors`` as it is), where
the full layers' walk is long and ``pages_freed`` must equal the rule's
count, and the faults that only THIS model can have, each put into the
PROGRAM's weights while the reference keeps the originals.  One engine a
weight seed:

- ``as configured``, at the gate's prompts and at each of ``long``
  (default 6000 and 12000 tokens), with the ``v2.schedule`` spans held to
  the mechanism as ``gate_probe_window.py`` holds them;
- ``sinks zeroed``: every ``attn_window/sink`` set to 0 (a softmax with a
  sink of logit 0 where the model has its seeded one);
- ``sinks sign-flipped`` and ``sinks on other heads`` (each layer's 64
  logits rolled by one head): a wrong sign and a wrong head-to-sink
  mapping, the two faults a kernel's tiling of the sink could make;
- ``values unscaled``: every ``wv`` divided by ``attention_value_scale``,
  which is the program run with ``v`` unscaled;
- ``weights through int8``: every matmul weight round-tripped through
  int8 (one scale an output column), the nearest precision below the
  stated bf16, at the gate's prompts and the long ones.  The
  configuration's tolerance has to refuse it.

    chiprun -- python3 benchmark/tools/gate_probe_mimo.py <workload> <seed>... [long=6000,12000] [blocks=<n>] [window_blocks=<n>]

Prints one row per variant, prompt and seed and writes them to
``chiprun_out/gate_probe/<workload>.mimo.json``; exits 1 where a reading
as configured fails the tolerance, the int8 reading passes it, or the
spans contradict the mechanism.  Whether the tolerance refuses the two
faults is REPORTED (``passes``) and decides nothing here: the
configuration's ``logit_rms_tolerance_why`` says which it leaves.
"""

from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def _with(params, name, fn):
    """``params`` with ``fn`` applied to leaf ``name`` of both kinds'
    attention stacks (where the kind has it)."""
    layers = dict(params["layers"])
    for kind in ("attn_full", "attn_window"):
        if name in layers.get(kind, {}):
            layers[kind] = {**layers[kind], name: fn(layers[kind][name])}
    return {**params, "layers": layers}


def main(argv, root: Path = ROOT, need_chip: bool = True) -> int:
    workload = argv[0]
    seeds = [int(s) for s in argv[1:] if "=" not in s]
    options = dict(s.split("=") for s in argv[1:] if "=" in s)
    longs = [int(n) for n in options.get("long", "6000,12000").split(",")
             if n]
    import jax
    import jax.numpy as jnp

    from benchmark.lib import device, serve_driver
    from benchmark.lib.manifest import load_cell, load_code
    from benchmark.lib.model import build_model
    from benchmark.lib.run import seed32
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.telemetry.tracing import Tracer

    cell = load_cell(root, workload)
    if need_chip:
        device.require_chips(cell.chips)
    device.setup_compile_cache()
    cfg = cell.config
    tol = float(cfg["logit_rms_tolerance"])
    model = build_model(cfg)
    if model.mixed is None or not model.sink_layers:
        raise SystemExit(f"{workload}: no layer's softmax carries a sink")
    probe = load_code(root, "tools", "gate_probe")
    window_probe = load_code(root, "tools", "gate_probe_window")
    engine_config = dict(cfg["engine_config"])
    memory = dict(engine_config["memory_config"])
    for key, name in (("blocks", "num_blocks"),
                      ("window_blocks", "window_blocks")):
        if key in options:
            memory[name] = int(options[key])
    engine_config["memory_config"] = memory
    scale = model.mixed.value_scale
    rows, failed = [], False

    def show(e, variant, seed, prompt):
        rows.append(dict(e, variant=variant, seed=seed, prompt=prompt,
                         passes=bool(e["rms"] <= tol)))
        print("GATE", json.dumps(rows[-1]), flush=True)
        return rows[-1]["passes"]

    round_trip = jax.jit(probe._int8, donate_argnums=0)
    for seed in seeds:
        eng = InferenceEngineV2(model, engine_config, seed=seed32(seed))
        jax.block_until_ready(eng.params)
        eng.tracer = Tracer(enabled=True)
        bs, window = eng.cfg.block_size, model.mixed.sliding_window
        cap = -(-(window + eng.scheduler.token_budget) // bs) + 1
        decode = serve_driver.CORRECT_DECODE
        gate = list(serve_driver.CORRECT_PROMPTS)

        def readings(variant, original, longs):
            passed = [show(serve_driver.logit_errors(cell, eng, model, seed,
                                                     original),
                           variant, seed, gate)]
            bad = False
            for n in longs:
                e = window_probe.long_prompt_errors(cell, eng, model, seed,
                                                    n, original)
                passed.append(show(e, variant, seed, n))
                # the last step begins at position n + decode - 1
                want = max(0, (n + decode - 1 - window) // bs)
                if e["pages_freed"] != want or e["window_pages_max"] > cap:
                    print(f"[probe] the spans contradict the mechanism: "
                          f"{e['pages_freed']} pages freed where {want} "
                          f"are due, at most {e['window_pages_max']} window "
                          f"pages held where {cap} are allowed", flush=True)
                    bad = True
            return passed, bad

        passed, bad = readings("as configured", None, longs)
        failed |= bad or not all(passed)
        # the two faults: into the program's weights, the reference keeps
        # the engine's own (the edited leaves are new arrays)
        base = eng.params
        for variant, edit in (
                ("sinks zeroed", lambda p: _with(p, "sink", jnp.zeros_like)),
                ("sinks sign-flipped", lambda p: _with(p, "sink",
                                                       jnp.negative)),
                ("sinks on other heads", lambda p: _with(
                    p, "sink", lambda b: jnp.roll(b, 1, axis=-1))),
                ("values unscaled", lambda p: _with(
                    p, "wv", lambda w: (w.astype(jnp.float32)
                                        / scale).astype(w.dtype)))):
            eng.params = edit(base)
            readings(variant, base, [])
        eng.params = base
        del base
        original = jax.device_get(eng.params)
        eng.params = jax.tree.map(
            lambda w: round_trip(w) if w.size > probe.BIG else w, eng.params)
        passed, bad = readings("weights through int8", original, longs)
        failed |= bad or any(passed)
        del eng, original
        gc.collect()

    out = root / "chiprun_out" / "gate_probe"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.mimo.json").write_text(json.dumps(rows, indent=1))
    return int(failed)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
