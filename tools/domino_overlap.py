#!/usr/bin/env python
"""On-chip Domino overlap measurement: capture an XPlane trace of the
tensor-parallel forward with and without Domino batch chunking and report
how much collective time XLA hid under compute.

Ref claim: blogs/deepspeed-domino/README.md:126 — Domino hides 50-100% of
the TP communication.  On TPU the overlap comes from giving XLA
independent per-chunk chains (runtime/domino.py); this tool turns the
indirect compile-level evidence (test_autotp_domino.py — separate
per-chunk psums) into a measured on-device overlap fraction.

NEEDS >= 2 real TPU devices (a 1-chip mesh has no TP collective to
measure).  Usage:

    python tools/domino_overlap.py [--chunks 2] [--steps 8] [--assert-min 0.3]

Prints one JSON line per variant and a final comparison line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--assert-min", type=float, default=None,
                    help="exit 1 unless domino overlap >= this fraction")
    ap.add_argument("--device-substr", default="TPU")
    args = ap.parse_args()

    import jax
    import numpy as np

    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    if len(jax.devices()) < 2:
        print(json.dumps({"error": "domino overlap needs >= 2 devices "
                                   f"(have {len(jax.devices())}); the TP "
                                   "collective does not exist on one chip"}))
        return 2

    import jax.numpy as jnp

    from deepspeed_tpu.models import get_model_config, init_params
    from deepspeed_tpu.models import transformer as tf_model
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.runtime.domino import domino_forward
    from deepspeed_tpu.utils.xplane import analyze_logdir

    n = len(jax.devices())
    topo = MeshTopology({"tensor": n})
    set_topology(topo)
    cfg = get_model_config("llama-tiny", hidden_size=1024,
                           intermediate_size=2816, num_layers=4,
                           num_heads=16, num_kv_heads=16, max_seq_len=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    from deepspeed_tpu.parallel.sharding import ShardingRules

    params = jax.device_put(
        params, ShardingRules(topo, zero_stage=0).tree_shardings(params))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(8, 512)), jnp.int32)

    def run(label, fn):
        out = fn(params, ids)          # compile outside the capture
        float(np.asarray(out.sum()))
        logdir = tempfile.mkdtemp(prefix=f"domino_{label}_")
        jax.profiler.start_trace(logdir)
        for _ in range(args.steps):
            out = fn(params, ids)
        float(np.asarray(out.sum()))   # hard device drain
        jax.profiler.stop_trace()
        stats = analyze_logdir(logdir, args.device_substr)
        print(json.dumps({"variant": label, **stats}))
        return stats

    plain = jax.jit(lambda p, i: tf_model.forward(p, i, cfg))
    domino = jax.jit(lambda p, i: domino_forward(p, i, cfg,
                                                 n_chunks=args.chunks))
    s_plain = run("plain_tp", plain)
    s_domino = run(f"domino_{args.chunks}chunk", domino)

    result = {
        "metric": "domino_overlap_fraction",
        "plain": s_plain.get("mean_overlap_fraction"),
        "domino": s_domino.get("mean_overlap_fraction"),
    }
    print(json.dumps(result))
    if args.assert_min is not None:
        ok = (s_domino.get("mean_overlap_fraction") or 0) >= args.assert_min
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
