#!/usr/bin/env python3
"""What the ``correct`` gate cannot see of a SELF-DRAFTING cell: the gate
drives ``put``, the main model one token at a time, and never a verify
run or the multi-token-prediction module.  This probe drives the drafted
path itself, one sequence an engine, and compares with the plain
reference (``<reference>.logits`` and ``.draft_logits``):

- the main model's logits at BOTH rows of the verify runs (the pending
  token's row, and the draft's row where the draft stood: a refused
  draft's row was computed on a token the stream does not hold),
- the module's logits at the rows its drafts were taken from,

each as rms(logit - reference) over the compared rows and the vocabulary,
a share of rms(reference), at a short prompt and at one past
``index_topk`` (``long=<n>``); then the same with every matmul weight
round-tripped through int8 (the reference keeps the originals); the
gate's own reading (``serve_driver.logit_errors``) beside each; and the
share of positions at which the self-drafted stream and a plain greedy
stream of the same engine (through ``put``) agree: equal by
construction in exact arithmetic, and a near-tie that bf16 rounds one
way in a two-row step and the other way in a one-row step may part them.

    chiprun -- python3 benchmark/tools/gate_probe_mtp.py <workload> <seed>... [long=<n>] [short=<n>] [decode=<n>] [blocks=<n>] [live=<n> answer=<n>]

With ``live=<n>`` also the SERVER's streams under load (``at_load``):
``n`` requests past ``index_topk``, half arriving while the others
decode, ``answer`` tokens each, against plain greedy streams of the same
engine, and the launch path's spans of those steps (``v2.h2d`` ``arrays``,
``v2.dispatch`` ``programs``, steps that held verify runs and chunks).

Prints one row per variant, prompt and seed and writes them to
``chiprun_out/gate_probe/<workload>.mtp.json``.
"""

from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


class HeadTap:
    """The logits the draft program takes its argmaxes of, out of every
    step: ``model._lm_head`` hands what it returns to the host.
    Installed before the engine traces a step; records only between
    ``start`` and ``take``."""

    def __init__(self, v2_model):
        self.seen = None
        head = v2_model._lm_head

        def tapped(x, params, cfg):
            import jax

            out = head(x, params, cfg)
            jax.debug.callback(self._record, out)
            return out

        v2_model._lm_head = tapped
        self.close = lambda: setattr(v2_model, "_lm_head", head)

    def start(self):
        self.seen = []

    def _record(self, out):
        if self.seen is not None:
            import numpy as np

            self.seen.append(np.asarray(out, np.float32))

    def take(self, slots: int):
        """One step's ``(trunk [2, slots, V], module [slots, V])``."""
        import jax

        jax.effects_barrier()
        seen, self.seen = self.seen, []
        by = {len(a): a for a in seen}
        return by[2 * slots].reshape(2, slots, -1), by[slots]


def drafted_errors(cell, eng, model, seed, n, decode, tap,
                   reference_params=None) -> dict:
    """One prompt of ``n`` tokens through self-drafting steps until
    ``decode`` tokens are delivered, every compared row against the
    reference over the delivered stream."""
    import jax
    import numpy as np

    rng = np.random.default_rng([seed % 2 ** 32, 79])
    uid = (1 << 30) + 9
    prompt = rng.integers(0, model.vocab_size, size=n).tolist()
    slots = eng.cfg.max_tracked_sequences + 1
    eng.admit(uid, prompt)
    seq = eng.state_manager.get(uid)
    stream = []
    trunk, module = {}, {}          # position -> the program's logits
    second = refused = 0
    tap.start()
    while len(stream) < decode:
        pos, drafted = seq.num_cached, seq.draft is not None
        decoding = seq.uncached == 1
        burst = eng.step_bursts().get(uid, [])
        both, mod = tap.take(slots)
        if not burst:
            continue                # a chunk that did not end the prompt
        eng.extend(uid, burst[-1])
        last = seq.num_cached - 1 if not decoding else pos
        if decoding and drafted:
            trunk[pos] = both[0, seq.slot]
            if len(burst) == 2:
                trunk[pos + 1] = both[1, seq.slot]
                second += 1
                last = pos + 1
            else:
                refused += 1
        else:
            trunk[last] = both[1, seq.slot]
        module[last] = mod[seq.slot]
        stream.extend(burst)
    tap.seen = None
    eng.flush(uid)

    tokens = np.asarray([prompt + stream])
    params = eng.params if reference_params is None else reference_params
    ref = cell.reference()
    want_trunk = np.asarray(ref.logits(params, tokens, cell.config,
                                       jax.devices()[0]))[0]
    want_mod = np.asarray(ref.draft_logits(params, tokens, cell.config,
                                           jax.devices()[0]))[0]

    def rms(got: dict, want, upto):
        at = sorted(p for p in got if p < upto)
        g = np.stack([got[p] for p in at]).astype(np.float64)
        w = want[at].astype(np.float64)
        return {"rms": float((((g - w) ** 2).sum() / (w ** 2).sum()) ** 0.5),
                "rows": len(at),
                "agree": int((g.argmax(-1) == w.argmax(-1)).sum())}

    total = tokens.shape[1]
    # plain greedy through put, the same engine and weights
    out = eng.put([uid], [prompt])
    while uid not in out:
        out = eng.put([], [])
    plain = []
    for _ in range(len(stream)):
        plain.append(int(np.asarray(out[uid]).argmax()))
        eng.extend(uid, plain[-1])
        out = eng.put([], [])
    eng.flush(uid)
    same = [a == b for a, b in zip(stream, plain)]
    return {"prompt": n, "delivered": len(stream),
            "verify_rows": rms(trunk, want_trunk, total),
            "module_rows": rms(module, want_mod, total - 1),
            "second_rows": second, "refused": refused,
            "accept_share": second / max(1, second + refused),
            "streams_agree_share": sum(same) / len(same),
            "first_parting": same.index(False) if False in same else -1}


def at_load(eng, model, seed, live, answer, shortest,
            server_config) -> dict:
    """``live`` requests with prompts of ``shortest`` tokens and up to
    half as many more (past ``index_topk``), half of them sent once the
    others decode, ``answer`` tokens each, through an ``InferenceServer``
    with spans on: the streams it delivered against plain greedy streams
    of the same engine (``put``, every sequence a row a step), and what
    each step's spans say of the launch path."""
    import time

    import numpy as np

    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    rng = np.random.default_rng([seed % 2 ** 32, 83])
    longest = min(shortest * 3 // 2, eng.cfg.max_context - answer - 2)
    prompts = [rng.integers(0, model.vocab_size, size=int(n)).tolist()
               for n in rng.integers(shortest, longest + 1, size=live)]
    before = eng.drafts_verified, eng.drafts_accepted
    srv = InferenceServer(eng, dict(server_config,
                                    tracing={"enabled": True}))
    srv.start()
    try:
        params = SamplingParams(max_new_tokens=answer)
        streams = [srv.submit(p, params) for p in prompts[:live // 2]]
        while len(streams[0].tokens) < 4 and not streams[0].done:
            time.sleep(0.01)        # the first half decodes by now
        streams += [srv.submit(p, params) for p in prompts[live // 2:]]
        drafted = [list(s) for s in streams]
    finally:
        srv.stop(drain=False, timeout=120)
    by = {}
    for e in srv.tracer.snapshot():
        if e.get("ph") == "X":
            by.setdefault(e["name"], []).append(e["args"])
    ran = [a for a in by.get("v2.schedule", []) if a.get("tokens")]
    eng.tracer = None

    uids = [(1 << 30) + 20 + k for k in range(live)]
    out = eng.put(uids, prompts)
    while len(out) < live:
        out.update(eng.put([], []))
    plain = [[] for _ in uids]
    for _ in range(answer):
        for k, uid in enumerate(uids):
            plain[k].append(int(np.asarray(out[uid]).argmax()))
            eng.extend(uid, plain[k][-1])
        out = eng.put([], [])
    for uid in uids:
        eng.flush(uid)
    same = [a == b for d, p in zip(drafted, plain) for a, b in zip(d, p)]
    partings = [next((i for i, (a, b) in enumerate(zip(d, p)) if a != b), -1)
                for d, p in zip(drafted, plain)]
    verified = eng.drafts_verified - before[0]
    return {"live": live, "answer": answer,
            "prompts": [len(p) for p in prompts],
            "positions": len(same),
            "streams_agree_share": sum(same) / len(same),
            "first_partings": partings,
            "drafts": verified,
            "accept_share": (eng.drafts_accepted - before[1])
            / max(1, verified),
            "steps": len(ran),
            "h2d_arrays": sorted({a["arrays"] for a in by["v2.h2d"]}),
            "dispatch_programs": sorted({a["programs"]
                                         for a in by["v2.dispatch"]}),
            "fetches_per_step": len(by["v2.fetch"]) / max(1, len(ran)),
            # a verify run's two rows count among the prefill tokens
            "steps_with_verify_runs_and_chunks": sum(
                1 for a in ran if a.get("verify_runs")
                and a["prefill_tokens"] > 2 * a["verify_runs"]),
            "verify_runs_max": max(a.get("verify_runs", 0) for a in ran)}


def main(argv, root: Path = ROOT, need_chip: bool = True) -> int:
    seeds = [int(s) for s in argv[1:] if "=" not in s]
    options = dict(s.split("=") for s in argv[1:] if "=" in s)
    workload = argv[0]
    prompts = [int(options.get("short", 300)), int(options.get("long", 2560))]
    decode = int(options.get("decode", 48))
    live, answer = int(options.get("live", 0)), int(options.get("answer", 256))
    import jax

    from benchmark.lib import device, serve_driver
    from benchmark.lib.manifest import load_cell, load_code
    from benchmark.lib.model import build_model
    from benchmark.lib.run import seed32
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2 import model as v2_model

    cell = load_cell(root, workload)
    if need_chip:
        device.require_chips(cell.chips)
    device.setup_compile_cache()
    cfg = cell.config
    tol = float(cfg["logit_rms_tolerance"])
    model = build_model(cfg)
    engine_config = dict(cfg["engine_config"])
    if not engine_config.get("self_draft"):
        raise SystemExit(f"{workload}: the engine does not draft for itself")
    if "blocks" in options:
        engine_config["memory_config"] = dict(
            engine_config["memory_config"], num_blocks=int(options["blocks"]))
    probe = load_code(root, "tools", "gate_probe")
    round_trip = jax.jit(probe._int8, donate_argnums=0)
    tap = HeadTap(v2_model)
    rows = []

    def read(variant, eng, seed, reference_params=None):
        gate = serve_driver.logit_errors(cell, eng, model, seed,
                                         reference_params)
        rows.append({"variant": variant, "seed": seed, "gate": gate,
                     "passes": bool(gate["rms"] <= tol)})
        print("GATE", json.dumps(rows[-1]), flush=True)
        for n in prompts:
            e = drafted_errors(cell, eng, model, seed, n, decode, tap,
                               reference_params)
            rows.append(dict(e, variant=variant, seed=seed))
            print("MTP", json.dumps(rows[-1]), flush=True)

    for seed in seeds:
        eng = InferenceEngineV2(model, engine_config, seed=seed32(seed))
        jax.block_until_ready(eng.params)
        read("as configured", eng, seed)
        if live:
            rows.append(dict(at_load(eng, model, seed, live, answer,
                                     prompts[1], cfg.get("server_config", {})),
                             variant="at load", seed=seed))
            print("LOAD", json.dumps(rows[-1]), flush=True)
        original = jax.device_get(eng.params)    # the reference's weights
        eng.params = jax.tree.map(
            lambda w: round_trip(w) if w.size > probe.BIG else w, eng.params)
        read("weights through int8", eng, seed, original)
        del eng, original
        gc.collect()

    tap.close()
    out = root / "chiprun_out" / "gate_probe"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{workload}.mtp.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
