#!/usr/bin/env python3
"""Sweep the flash kernels' forms and block edges at given shapes, on the chip.

    python tools/bench_flash_blocks.py [--iters 20] [--out chiprun_out/flash_blocks.json]
        [--shape B,Hq,Hkv,S,D[,window]] ... [--variant NAME] ...

A variant forces what ``flash_mha.plan`` would decide, for all three
kernels (forward, dq, dkv):

    chosen            what plan() decides as the tree stands
    oneshot           the resident one-shot kernels (the whole [bq, S] score
                      block, masked everywhere), the blocked backward where
                      the one-shot one does not fit VMEM: the program of
                      every call before PR 47
    live:BQ:BK        the resident kernels over the live part only, one
                      program a head, q blocks of BQ and key chunks of BK
    blocked:BQ:BK     the 4D KV-blocked grid at those tiles

Each variant runs ``iters`` forward + backward calls under one profiler
trace; the table gives the DEVICE time a call of each kernel (by the kernel's
name in the trace), the rate on the live pairs (what the roofline counts) and
the worst difference of its outputs from ``oneshot``'s.  Default shapes: the
two train cells' (gpt2-350m micro 8 at 1024; opt-1.3b micro 2 at 2048).
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import math
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

fm = importlib.import_module("deepspeed_tpu.ops.pallas.flash_mha")

SHAPES = ["8,16,16,1024,64", "2,32,32,2048,64"]
VARIANTS = ["oneshot", "chosen", "live:128:128", "live:256:256",
            "live:512:512", "blocked:256:256", "blocked:512:512"]
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
_PLAN = fm.plan


def forced_plan(variant: str):
    """A stand-in for ``fm.plan`` that takes the variant's path."""
    if variant == "chosen":
        return _PLAN
    if variant == "oneshot":
        def plan(s, d, group=1, causal=True, window=None):
            dense = _PLAN(s, d, group, False, None)     # routing of a dense call
            return _PLAN(s, d, group, causal, window)._replace(
                fwd=dense.fwd, dq=dense.dq, dkv=dense.dkv)
        return plan
    path, bq, bk = variant.split(":")
    bq, bk = int(bq), int(bk)

    def plan(s, d, group=1, causal=True, window=None):
        kp = functools.partial(fm._kernel_plan, s=s, causal=causal,
                               window=window)
        return _PLAN(s, d, group, causal, window)._replace(
            fwd=kp(path, bq, bk), dq=kp(path, bq, bk),
            dkv=kp(path, bq, bk, by_k=path == "live"))
    return plan


def kernel_seconds(logdir: str) -> dict:
    """Device seconds by kernel family, summed over the trace."""
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    out = dict.fromkeys(KERNELS, 0.0)
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if "tpu_custom_call" not in ev.name:
                    continue
                name = ev.name.partition(" = ")[0].lstrip("%")
                # longest family first: flash_bwd_dq / _dkv before flash_fwd
                for fam in sorted(KERNELS, key=len, reverse=True):
                    if name.startswith(fam):
                        out[fam] += ev.duration_ns * 1e-9
                        break
    return out


def run_variant(variant, shape, iters, seed):
    b, hq, hkv, s, d, *rest = shape
    window = rest[0] if rest else None
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    g = jax.random.normal(ks[3], (b, hq, s, d), jnp.bfloat16)
    scale = 1.0 / math.sqrt(d)
    fm.plan = forced_plan(variant)
    if variant.startswith("blocked"):
        fm._BLK_Q, fm._BLK_K = (int(x) for x in variant.split(":")[1:])
    try:
        @jax.jit
        def step(q, k, v, g):
            o, lse = fm._fwd(q, k, v, True, scale, window=window)
            return (o,) + fm._bwd_impl(q, k, v, o, lse, g, True, scale,
                                       window=window)

        outs = jax.block_until_ready(step(q, k, v, g))       # compile + warm
        logdir = tempfile.mkdtemp(prefix="flash_blocks_")
        jax.profiler.start_trace(logdir)
        for _ in range(iters):
            r = step(q, k, v, g)
        jax.block_until_ready(r)
        jax.profiler.stop_trace()
        secs = kernel_seconds(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
    finally:
        fm.plan, fm._BLK_Q, fm._BLK_K = _PLAN, None, None
    return outs, {kname: t / iters * 1e3 for kname, t in secs.items()}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", action="append")
    ap.add_argument("--variant", action="append")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/flash_blocks.json")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench_flash_blocks: needs a TPU, found {dev}")
    rows = []
    for text in args.shape or SHAPES:
        shape = tuple(int(x) for x in text.split(","))
        b, hq, hkv, s, d = shape[:5]
        live = s * (s + 1) // 2 if len(shape) == 5 else fm.plan(
            s, d, hq // hkv, True, shape[5]).live_pairs
        base = None
        for variant in args.variant or VARIANTS:
            try:
                outs, ms = run_variant(variant, shape, args.iters, args.seed)
            except Exception as e:  # a variant the compiler refuses is a row
                rows.append({"shape": text, "variant": variant,
                             "error": f"{type(e).__name__}: {e}"[:300]})
                print(json.dumps(rows[-1]), flush=True)
                continue
            if base is None:
                base = outs
            diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                             - b_.astype(jnp.float32))))
                       for a, b_ in zip(outs, base))
            # matrix products a pair: 2 forward, 3 dq, 4 dkv; 2 FLOPs x d
            tf = {kname: b * hq * live * n * 2 * d / (ms[kname] * 1e-3) / 1e12
                  for kname, n in zip(KERNELS, (2, 3, 4)) if ms[kname]}
            rows.append({"shape": text, "variant": variant,
                         "ms": {k_: round(t, 4) for k_, t in ms.items()},
                         "ms_total": round(sum(ms.values()), 4),
                         "live_tflops": {k_: round(t, 1)
                                         for k_, t in tf.items()},
                         "max_abs_diff_vs_first": diff})
            print(json.dumps(rows[-1]), flush=True)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"device": dev.device_kind, "iters": args.iters,
                               "rows": rows}, indent=1))


if __name__ == "__main__":
    main()
