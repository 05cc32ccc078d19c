#!/usr/bin/env python3
"""Does the ``correct`` gate of a cell whose model selects its keys (a
learned indexer over latent cache rows) hold the selection, and which
lower precision than the configuration states does it refuse?  The gate's
own prompts (``serve_driver.CORRECT_PROMPTS``, 300 and 290 tokens) lie
under ``index_topk``, where every key is chosen, so this probe also
compares at a LONG prompt, where the selection is active.  One engine a
weight seed:

- ``as configured``, at the gate's prompts (``more=<n>`` further readings
  with other token ids) and at ``long`` tokens: prefill in chunks, then
  ``serve_driver.CORRECT_DECODE`` tokens through the cache, against the
  plain reference's full forward.  A key the program chose and the
  reference did not reads, on seeded weights, as a large difference of the
  logits whatever the arithmetic (attention over 2048 seeded keys is an
  incoherent sum), so at the long prompt the reference ATTENDS over the
  program's own chosen sets (every full layer's, every row's, tapped out
  of the step by a host callback) and the sets' overlap with the
  reference's own choice is reported beside the logits' rms: the one number
  holds the arithmetic over the chosen keys, the other the choice.
  ``own=1`` adds the reading with the reference attending over its own;
- ``latent rows through int8``: after every step both pools of latent
  rows (the pages and the window layers' rings) are rounded to int8 with
  one scale a row and back, so what a step reads is what an int8 cache
  would hold;
- ``expert weights through int8`` and ``weights through int8``: the routed
  experts' three matrices, then every matmul weight, round-tripped
  through int8 (one scale an output column); the reference keeps the
  originals, on the host.

    chiprun -- python3 benchmark/tools/gate_probe_dsa.py <workload> <long> <seed>... [blocks=<n>] [more=<n>] [own=1] [long_seeds=<n>]

``blocks=<n>`` gives the engine a pool of ``n`` pages in place of the
configuration's (the float32 reference of a long prompt needs room);
``long_seeds=<n>``: only the first ``n`` seeds read the long prompt.

Prints one row per variant, prompt and seed and writes them to
``chiprun_out/gate_probe/<workload>.dsa.json``.
"""

from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


class SelectionTap:
    """Every full layer's chosen key positions, row by row, out of the
    steps of ONE sequence: ``latent.select_keys`` hands what it returns to
    the host beside the rows' positions.  Installed before the engine
    traces a step; records only between ``start`` and ``stop``."""

    def __init__(self, latent):
        self.rows = None
        self._at = {}
        full_layer, select = latent._full_layer, latent.select_keys

        def tapped_layer(x, ln1, p, cache_k, cache_v, layer, meta, cfg):
            # a full layer is traced alone, never inside a scan: a number
            self._at = {"layer": int(layer), "pos": meta[0], "slot": meta[2]}
            return full_layer(x, ln1, p, cache_k, cache_v, layer, meta, cfg)

        def tapped_select(scores, topk):
            import functools

            import jax

            sel, ok = select(scores, topk)
            jax.debug.callback(
                functools.partial(self._record, self._at["layer"]),
                self._at["pos"], self._at["slot"], sel, ok)
            return sel, ok

        latent._full_layer, latent.select_keys = tapped_layer, tapped_select
        self._restore = lambda: (setattr(latent, "_full_layer", full_layer),
                                 setattr(latent, "select_keys", select))

    def close(self):
        self._restore()

    def start(self, padding_slot: int):
        self.rows, self._padding = {}, padding_slot

    def _record(self, layer, pos, slot, sel, ok):
        if self.rows is None:
            return
        import numpy as np

        pos, sel = np.asarray(pos), np.asarray(sel)
        ok, real = np.asarray(ok), np.asarray(slot) != self._padding
        for i in np.flatnonzero(real):
            self.rows[layer, int(pos[i])] = sel[i][ok[i]]

    def stop(self, n_rows: int, topk: int):
        """One ``[n_rows, topk]`` array of positions a full layer, -1
        where a row chose fewer."""
        import jax
        import numpy as np

        jax.effects_barrier()
        rows, self.rows = self.rows, None
        layers = sorted({layer for layer, _ in rows})
        out = [np.full((n_rows, topk), -1, np.int32) for _ in layers]
        for (layer, pos), keys in rows.items():
            out[layers.index(layer)][pos, :len(keys)] = keys
        missing = n_rows * len(layers) - len(rows)
        if missing:
            raise RuntimeError(f"the tap saw no selection for {missing} rows")
        return out


def long_prompt_errors(cell, eng, model, seed, n, tap, reference_params=None,
                       own=False):
    """``serve_driver.logit_errors`` for ONE prompt of ``n`` tokens, the
    reference attending over the program's chosen sets, and those sets
    beside the reference's own."""
    import jax
    import numpy as np

    from benchmark.lib import serve_driver

    decode = serve_driver.CORRECT_DECODE
    rng = np.random.default_rng([seed % 2 ** 32, 78])
    uid = (1 << 30) + 7
    prompt = rng.integers(0, model.vocab_size, size=n).tolist()
    tap.start(eng.cfg.max_tracked_sequences)
    out = eng.put([uid], [prompt])
    while uid not in out:
        out = eng.put([], [])
    rows, toks = [np.asarray(out[uid], np.float32)], []
    for _ in range(decode):
        toks.append(int(rows[-1].argmax()))
        eng.extend(uid, toks[-1])
        rows.append(np.asarray(eng.put([], [])[uid], np.float32))
    eng.flush(uid)
    chosen = tap.stop(n + decode, model.mla.index_topk)
    got = np.stack(rows).astype(np.float64)

    def against(forced, sets=None):
        ref = np.asarray(cell.reference().logits(
            eng.params if reference_params is None else reference_params,
            np.asarray([prompt + toks]), cell.config, jax.devices()[0],
            last=decode + 1, selected=sets, forced=forced))[0].astype(
                np.float64)
        return {"rms": float((((got - ref) ** 2).sum()
                              / (ref ** 2).sum()) ** 0.5),
                "max": float(np.abs(got - ref).max() / np.abs(ref).max()),
                "agree": int((got.argmax(-1) == ref.argmax(-1)).sum())}

    sets = []
    e = dict(against(chosen, sets), positions=len(got), prompt=n)
    # the reference's own choice, every row past index_topk, layer by
    # layer (a later layer's on the stream the forced sets gave it)
    past = np.arange(model.mla.index_topk, n + decode)
    e["overlap_mean"], e["overlap_min"] = [], []
    for mine, theirs in zip(chosen, sets):
        theirs = np.asarray(theirs)
        hit = np.take_along_axis(theirs[past], np.maximum(mine[past], 0), 1)
        share = (hit & (mine[past] >= 0)).sum(1) / theirs[past].sum(1)
        e["overlap_mean"].append(float(share.mean()))
        e["overlap_min"].append(float(share.min()))
    if own:
        e["rms_own_sets"] = against(None)["rms"]
    return e


def main(argv, root: Path = ROOT, need_chip: bool = True) -> int:
    workload, long_n = argv[0], int(argv[1])
    seeds = [int(s) for s in argv[2:] if "=" not in s]
    options = dict(s.split("=") for s in argv[2:] if "=" in s)
    blocks = [int(options["blocks"])] if "blocks" in options else []
    more = int(options.get("more", 0))
    own = bool(int(options.get("own", 0)))
    long_seeds = int(options.get("long_seeds", len(seeds)))
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
    if model.mla is None:
        raise SystemExit(f"{workload}: the model has no latent attention")
    from deepspeed_tpu.inference.v2 import latent

    tap = SelectionTap(latent)
    probe = load_code(root, "tools", "gate_probe")
    int8, BIG = probe._int8, probe.BIG
    rows = []

    def show(e, variant, seed, prompt):
        rows.append(dict(e, variant=variant, seed=seed, prompt=prompt,
                         passes=bool(e["rms"] <= tol)))
        print("GATE", json.dumps(rows[-1]), flush=True)

    def gate(variant, eng, seed, reference_params=None):
        show(serve_driver.logit_errors(cell, eng, model, seed,
                                       reference_params),
             variant, seed, list(serve_driver.CORRECT_PROMPTS))

    engine_config = dict(cfg["engine_config"])
    if blocks:
        engine_config["memory_config"] = dict(engine_config["memory_config"],
                                              num_blocks=blocks[0])

    @jax.jit
    def rows_through_int8(pool):
        x = pool.astype(jnp.float32)
        s = jnp.maximum(jnp.abs(x).max(axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.clip(jnp.rint(x / s), -127, 127) * s).astype(pool.dtype)
    round_trip = jax.jit(int8, donate_argnums=0)
    failed = False

    for seed in seeds:
        # the programs traced under the tap keep it; no later engine of
        # this process is traced without
        eng = InferenceEngineV2(model, engine_config, seed=seed32(seed))
        jax.block_until_ready(eng.params)
        gate("as configured", eng, seed)
        for k in range(more):
            gate("as configured", eng, seed * 1000 + k)

        carried = eng._carried

        def through_int8(out, eng=eng, carried=carried):
            out = carried(out)
            eng.cache_k = rows_through_int8(eng.cache_k)
            eng.state = {"win": rows_through_int8(eng.state["win"])}
            return out
        eng._carried = through_int8
        for k in range(3):
            gate("latent rows through int8", eng, seed * 1000 + k)
        eng._carried = carried
        if seed in seeds[:long_seeds]:
            try:
                show(long_prompt_errors(cell, eng, model, seed, long_n, tap,
                                        own=own),
                     "as configured", seed, long_n)
            except Exception:       # the short readings are worth keeping
                import traceback

                traceback.print_exc()
                failed = True

        original = jax.device_get(eng.params)    # the reference's weights
        moe = eng.params["layers"]["moe"]
        for k in ("wg", "wi", "wo"):
            moe[k] = round_trip(moe[k])
        for k in range(2):
            gate("expert weights through int8", eng, seed * 1000 + k,
                 original)
        eng.params = jax.tree.map(
            lambda w: round_trip(w) if w.size > BIG else w, eng.params)
        for k in range(2):
            gate("weights through int8", eng, seed * 1000 + k, original)
        del eng, original, moe, carried, through_int8
        gc.collect()

    tap.close()
    out = root / "chiprun_out" / "gate_probe"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.dsa.json").write_text(json.dumps(rows, indent=1))
    return int(failed)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
