"""Ablation timings for the train step: fwd / fwd+bwd / full, attention
impls, micro-batch shapes. Run on the real chip. Not part of the suite."""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(f, *args, iters=6):
    r = f(*args)
    np.asarray(jax.tree_util.tree_leaves(r)[0]).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(iters):
        r = f(*args)
    np.asarray(jax.tree_util.tree_leaves(r)[0]).ravel()[:1]
    return (time.perf_counter() - t0) / iters


import jax
import jax.numpy as jnp


def main():
    from deepspeed_tpu.models import get_model_config, init_params
    from deepspeed_tpu.models import transformer as tf
    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()

    seq = 1024
    rng = np.random.default_rng(0)

    for label, kw in [
        ("flash", {}),
        ("xla-attn", {"attn_impl": "xla"}),
        ("flash-remat-none", {"remat_policy": "none"}),
    ]:
        for b in (8, 16):
            cfg = get_model_config("gpt2-350m", max_seq_len=seq, **kw)
            params = init_params(cfg, jax.random.PRNGKey(0))
            params = jax.tree.map(lambda x: x, params)  # fresh
            ids = rng.integers(0, cfg.vocab_size, size=(b, seq + 1), dtype=np.int32)
            batch = {"input_ids": jnp.asarray(ids[:, :-1]),
                     "labels": jnp.asarray(ids[:, 1:])}

            fwd = jax.jit(lambda p, bt: tf.loss_fn(p, bt, cfg))
            gfn = jax.jit(lambda p, bt: jax.value_and_grad(
                lambda pp: tf.loss_fn(pp, bt, cfg))(p))
            try:
                t_f = timeit(fwd, params, batch)
            except Exception as e:
                print(f"{label} b={b} fwd FAILED {str(e)[:80]}"); continue
            try:
                t_g = timeit(gfn, params, batch)
            except Exception as e:
                print(f"{label} b={b} fwd={b*seq/t_f:,.0f} tok/s; grad FAILED {str(e)[:80]}")
                continue
            ftok, gtok = b * seq / t_f, b * seq / t_g
            print(f"{label:18s} b={b:2d}: fwd {ftok:9,.0f} tok/s ({t_f*1e3:6.1f} ms)"
                  f" | fwd+bwd {gtok:9,.0f} tok/s ({t_g*1e3:6.1f} ms)", flush=True)


if __name__ == "__main__":
    main()
