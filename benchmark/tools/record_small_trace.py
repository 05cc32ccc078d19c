#!/usr/bin/env python3
"""Record the small device trace that tests/benchmark checks the trace
reduction against, and print what a trace of this chip looks like.

    chiprun -- python3 benchmark/tools/record_small_trace.py

Runs on the chip only.  Traces about 0.1 s: a bf16 matmul program a few
times, the repo's Pallas flash kernel (forward and backward) a few times,
a 30 ms host sleep in which the device is idle, and the matmuls again.
Writes ``chiprun_out/small_trace/small_trace.xplane.pb`` and a summary of
every plane, line and event name beside it.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.flash_attention import flash_attention

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: platform {devs[0].platform!r}", file=sys.stderr)
        return 1
    out = ROOT / "chiprun_out" / "small_trace"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)

    @jax.jit
    def matmuls(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    def attn_loss(q, k, v):
        return flash_attention(q, k, v, impl="pallas").astype(
            jnp.float32).sum()

    attn = jax.jit(jax.value_and_grad(attn_loss, argnums=(0, 1, 2)))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1024, 2048), jnp.bfloat16)
    w = jax.random.normal(key, (2048, 2048), jnp.bfloat16) * 0.02
    q = jax.random.normal(key, (2, 1024, 16, 64), jnp.bfloat16)

    jax.block_until_ready((matmuls(x, w), attn(q, q, q)))       # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out / "raw"), profiler_options=opts)
    t0 = time.perf_counter()
    for _ in range(3):
        r = matmuls(x, w)
    for _ in range(2):
        g = attn(q, q, q)
    jax.block_until_ready((r, g))
    time.sleep(0.03)
    for _ in range(3):
        r = matmuls(x, w)
    jax.block_until_ready(r)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()

    pbs = glob.glob(str(out / "raw" / "**" / "*.xplane.pb"), recursive=True)
    dst = out / "small_trace.xplane.pb"
    shutil.copy(pbs[0], dst)
    shutil.rmtree(out / "raw")
    print(f"trace {dst} {os.path.getsize(dst)} bytes, host window "
          f"{window_s * 1e3:.1f} ms")

    prof = jax.profiler.ProfileData.from_file(str(dst))
    summary = []
    for plane in prof.planes:
        lines = []
        for line in plane.lines:
            names = Counter()
            dur = Counter()
            first, last = None, None
            for ev in line.events:
                names[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                first = ev.start_ns if first is None else min(first, ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                last = end if last is None else max(last, end)
            lines.append({"line": line.name, "events": sum(names.values()),
                          "first_ns": first, "last_ns": last,
                          "top": [[n, c, dur[n]] for n, c in
                                  names.most_common(40)]})
        summary.append({"plane": plane.name, "lines": lines})
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    for p in summary:
        print("PLANE", p["plane"])
        for ln in p["lines"]:
            print("  LINE", ln["line"], ln["events"], ln["first_ns"],
                  ln["last_ns"])
            if "TPU" in p["plane"] or "tpu" in p["plane"]:
                for n, c, d in ln["top"][:25]:
                    print("     ", c, d, n[:150])
    # one event's stats, to see what the device events carry
    for plane in prof.planes:
        if "TPU" in plane.name:
            for line in plane.lines:
                for ev in line.events:
                    print("STATS", plane.name, line.name, ev.name[:80],
                          dict(list(ev.stats)[:12]))
                    break
            break
    print(json.dumps({"ok": True, "kind": devs[0].device_kind,
                      "count": len(devs),
                      "memory_stats": {k: v for k, v in
                                       (devs[0].memory_stats() or {}).items()
                                       if "bytes" in k}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
