#!/usr/bin/env python3
"""Does the ``correct`` gate of a cell whose model mixes window and full
attention by layer hold the window, and which lower precision than the
configuration states does it refuse?  The gate's own prompts
(``serve_driver.CORRECT_PROMPTS``, 300 and 290 tokens) lie UNDER the
window, where a window layer reads what a full layer reads and no page is
ever freed: the window's mask, the lower edge of the kernel's page walk
and the allocator that returns pages behind the window are invisible to
it.  So this probe also compares at LONG prompts, past the window.  One
engine a weight seed:

- ``as configured``, at the gate's prompts and at each of ``long``
  (default 6000 and 12000 tokens): prefill in chunks of the step's
  budget, then ``serve_driver.CORRECT_DECODE`` tokens through the cache,
  against the plain reference's full forward (dense masks, in blocks).
  While a long prompt runs the engine's ``v2.schedule`` spans are
  recorded and held to the mechanism: ``pages_freed`` summed over the
  steps is what the allocator's rule gives for that many positions, and
  ``window_pages`` never passes ``ceil((window + budget) / page) + 1``;
- ``weights through int8``: every matmul weight round-tripped through
  int8 (one scale an output column), the nearest precision below the
  stated bf16; the reference keeps the originals, on the host.  The
  configuration's tolerance has to refuse it.

    chiprun -- python3 benchmark/tools/gate_probe_window.py <workload> <seed>... [long=6000,12000] [blocks=<n>] [window_blocks=<n>]

``blocks`` / ``window_blocks`` give the engine smaller pools than the
configuration's (the float32 reference of a long prompt needs room).

Prints one row per variant, prompt and seed and writes them to
``chiprun_out/gate_probe/<workload>.window.json``; exits 1 where a
reading as configured fails the tolerance, the int8 reading passes it, or
the spans contradict the mechanism.
"""

from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def long_prompt_errors(cell, eng, model, seed, n, reference_params=None):
    """``serve_driver.logit_errors`` for ONE prompt of ``n`` tokens, and
    what the ``v2.schedule`` spans of its steps said of the two pools."""
    import jax
    import numpy as np

    from benchmark.lib import serve_driver

    decode = serve_driver.CORRECT_DECODE
    rng = np.random.default_rng([seed % 2 ** 32, 79])
    uid = (1 << 30) + 9
    prompt = rng.integers(0, model.vocab_size, size=n).tolist()
    eng.tracer.clear()
    out = eng.put([uid], [prompt])
    while uid not in out:
        out = eng.put([], [])
    rows, toks = [np.asarray(out[uid], np.float32)], []
    for _ in range(decode):
        toks.append(int(rows[-1].argmax()))
        eng.extend(uid, toks[-1])
        rows.append(np.asarray(eng.put([], [])[uid], np.float32))
    eng.flush(uid)
    steps = [e["args"] for e in eng.tracer.snapshot()
             if e.get("ph") == "X" and e["name"] == "v2.schedule"
             and "window_pages" in e["args"]]
    got = np.stack(rows).astype(np.float64)
    ref = np.asarray(cell.reference().logits(
        eng.params if reference_params is None else reference_params,
        np.asarray([prompt + toks]), cell.config, jax.devices()[0],
        last=decode + 1))[0].astype(np.float64)
    return {"rms": float((((got - ref) ** 2).sum()
                          / (ref ** 2).sum()) ** 0.5),
            "max": float(np.abs(got - ref).max() / np.abs(ref).max()),
            "agree": int((got.argmax(-1) == ref.argmax(-1)).sum()),
            "positions": len(got), "prompt": n, "steps": len(steps),
            "pages_freed": sum(a["pages_freed"] for a in steps),
            "window_pages_max": max(a["window_pages"] for a in steps),
            "full_pages_max": max(a["full_pages"] for a in steps)}


def main(argv, root: Path = ROOT, need_chip: bool = True) -> int:
    workload = argv[0]
    seeds = [int(s) for s in argv[1:] if "=" not in s]
    options = dict(s.split("=") for s in argv[1:] if "=" in s)
    longs = [int(n) for n in options.get("long", "6000,12000").split(",")
             if n]
    import jax

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
    if model.mixed is None:
        raise SystemExit(f"{workload}: the model keeps one kind of layer")
    probe = load_code(root, "tools", "gate_probe")
    engine_config = dict(cfg["engine_config"])
    memory = dict(engine_config["memory_config"])
    for key, name in (("blocks", "num_blocks"),
                      ("window_blocks", "window_blocks")):
        if key in options:
            memory[name] = int(options[key])
    engine_config["memory_config"] = memory
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
        original = None
        for variant in ("as configured", "weights through int8"):
            if original is None and variant != "as configured":
                original = jax.device_get(eng.params)
                eng.params = jax.tree.map(
                    lambda w: round_trip(w) if w.size > probe.BIG else w,
                    eng.params)
            passed = [show(serve_driver.logit_errors(cell, eng, model, seed,
                                                     original),
                           variant, seed, list(serve_driver.CORRECT_PROMPTS))]
            for n in longs:
                e = long_prompt_errors(cell, eng, model, seed, n, original)
                passed.append(show(e, variant, seed, n))
                # the last step begins at position n + decode - 1
                want = max(0, (n + decode - 1 - window) // bs)
                if e["pages_freed"] != want or e["window_pages_max"] > cap:
                    print(f"[probe] the spans contradict the mechanism: "
                          f"{e['pages_freed']} pages freed where {want} "
                          f"are due, at most {e['window_pages_max']} window "
                          f"pages held where {cap} are allowed", flush=True)
                    failed = True
            # as configured every reading passes; through int8 none does
            failed |= (not all(passed) if original is None else any(passed))
        del eng, original
        gc.collect()

    out = root / "chiprun_out" / "gate_probe"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.window.json").write_text(json.dumps(rows, indent=1))
    return int(failed)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
