"""On-chip micro-benchmarks for the Pallas device kernels vs their XLA/jnp
equivalents: fused AdamW step and blockwise int8 quantize.  Each variant
iterates K times INSIDE one jit (lax.scan) so one dispatch covers the
whole timed window and per-call dispatch does not swamp the kernel.
Not part of the suite."""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

ITERS = 30


def timeit(f, *args):
    """f must iterate ITERS times inside one jit AND return a scalar —
    fetching a full-size array would time the download, not the kernel."""
    r = f(*args)
    assert getattr(r, "ndim", 0) == 0, "bench fns must reduce to a scalar"
    float(np.asarray(r))
    t0 = time.perf_counter()
    float(np.asarray(f(*args)))
    return (time.perf_counter() - t0) / ITERS


def bench_adamw():
    from deepspeed_tpu.runtime.optimizers import build_optimizer

    rng = np.random.default_rng(0)
    shapes = {"wte": (50257, 1024), "h": (24, 1024, 4096),
              "h2": (24, 4096, 1024), "qkv": (24, 1024, 3072),
              "ln": (48, 1024)}
    params = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
              for k, s in shapes.items()}
    grads = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
             for k, s in shapes.items()}
    n = sum(int(np.prod(s)) for s in shapes.values())
    bytes_moved = n * 4 * 7  # read p,g,m,v; write p,m,v

    for label, cfg in [("optax", {}), ("pallas", {"pallas_fused": True})]:
        opt = build_optimizer("adamw", dict({"weight_decay": 0.01}, **cfg))
        state = opt.init(params)

        @jax.jit
        def run(g, s, p):
            def body(carry, _):
                p_, s_ = carry
                p2, s2 = opt.update(g, s_, p_, 1e-4)
                return (p2, s2), ()

            (p, s), _ = lax.scan(body, (p, s), None, length=ITERS)
            return sum(jnp.sum(x) for x in jax.tree.leaves(p))

        dt = timeit(run, grads, state, params)
        print(f"adamw/{label}: {dt*1e3:.2f} ms/step  "
              f"({bytes_moved/dt/1e9:.0f} GB/s effective, {n/1e6:.0f}M "
              f"params)", flush=True)


def bench_quantize():
    from deepspeed_tpu.ops import quantizer as qz

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8192, 8192)), jnp.bfloat16)
    nbytes = x.size * 2 + x.size + x.size // 256 * 4

    for label, backend in [("jnp", "jnp"), ("pallas", "pallas")]:

        @jax.jit
        def roundtrip(t):
            # chain the round-trips so scan cannot elide iterations
            def body(cur, _):
                q, s, _ = qz.quantize_blockwise(cur, 8, 256, backend=backend)
                return qz.dequantize_blockwise(
                    q, s, dtype=jnp.bfloat16, backend=backend), ()

            out, _ = lax.scan(body, t, None, length=ITERS)
            return jnp.sum(out.astype(jnp.float32))

        dt = timeit(roundtrip, x)
        print(f"quant+dequant/{label}: {dt*1e3:.2f} ms/iter  "
              f"({2*nbytes/dt/1e9:.0f} GB/s effective, {x.size/1e6:.0f}M "
              f"elems)", flush=True)

        @jax.jit
        def fq(t):
            def body(cur, _):
                return qz.fake_quantize(cur, 8, 256, backend=backend), ()

            out, _ = lax.scan(body, t, None, length=ITERS)
            return jnp.sum(out.astype(jnp.float32))

        dt = timeit(fq, x)
        print(f"fake_quantize/{label}: {dt*1e3:.2f} ms/iter", flush=True)


if __name__ == "__main__":
    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    print(f"backend: {jax.default_backend()}  devices: {jax.devices()}")
    bench_adamw()
    bench_quantize()
