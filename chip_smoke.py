#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                # one TPU chip: device, kernels, train, serve
    python chip_smoke.py --four-chips   # four chips: sharded train + routed replicas

Drives the two main paths once through the entry points a user calls
(``ds.initialize`` → ``engine.train_batch``; ``InferenceEngineV2`` +
``InferenceServer``) at the published widths of gpt2-350m and mistral-7b,
with random weights and data made from ``--seed``, in ONE process, and
checks what comes out by the repo's own means.  It is not a benchmark:
every time it prints is a smoke reading of one run.

Fails (exit code != 0, no result line) when JAX finds no TPU, when any
phase fails, and anywhere the ``deepspeed_tpu`` package is not beside it.
The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from importlib import metadata

import numpy as np

# One-chip sizes.  gpt2-350m trains at its full published width and depth
# (ZeRO-1 AdamW in bf16, gas 2).  mistral-7b
# serves at its published widths with bf16 weights; all 32 layers are
# 13.6 GiB of them, and the compiler's own count for a 15.75 GiB v5e
# (weights + the KV pool + up to 2.5 pool-sized copies the fused decode
# loop keeps as temporaries) is 14.9 GiB at 28 layers and 12.9 GiB at 24
# with this 8192-row pool — so depth is cut to 24.
TRAIN_MODEL, TRAIN_SEQ, TRAIN_MICRO_BATCH, TRAIN_GAS, TRAIN_STEPS = (
    "gpt2-350m", 1024, 8, 2, 8)
SERVE_MODEL, SERVE_LAYERS = "mistral-7b", 24
SERVE_ENGINE = {"memory_config": {"num_blocks": 512, "block_size": 16},
                "max_context": 2048}
SERVE_REQUESTS, SERVE_PROMPT_LEN, SERVE_NEW_TOKENS = 4, 48, 24
# bf16 losses of the same batch on two meshes differ by reduction order
MESH_LOSS_TOL = 0.05
# A greedy stream may leave its reference only at a near-tie: where both
# tokens' logits lie within this share of the largest |logit| of the top.
# Read on the chip (PR 23): the same 8 prompts through generate() as one
# batch and one at a time part ways in 7 of 8 requests within 24 tokens,
# at gaps of up to 1.45 % of the largest |logit| (0.06 of ~4.2; the logits'
# standard deviation is 1.0) — bf16 activations, 24 layers.  Twice that:
TIE_TOL = 8 / 256

_cache_events = {"hits": 0, "misses": 0}


class SmokeFailure(Exception):
    """A phase saw something wrong; the run exits non-zero."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _count_cache_event(event: str, **_) -> None:
    if event.endswith("/cache_hits"):
        _cache_events["hits"] += 1
    elif event.endswith("/cache_misses"):
        _cache_events["misses"] += 1


def _hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"{dev.device_kind} #{dev.id} HBM in_use="
            f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB peak="
            f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")


def _release() -> None:
    """Drop the previous phase's device state before the next one."""
    import jax

    from deepspeed_tpu.parallel.topology import set_topology

    set_topology(None)
    gc.collect()
    jax.clear_caches()


# ----------------------------------------------------------------------
# device
# ----------------------------------------------------------------------
def device_phase(n_chips: int) -> dict:
    """The device as JAX reports it; anything but ``n_chips`` TPUs fails."""
    import jax
    import jaxlib

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    require(dev["platform"] == "tpu",
            f"no TPU: jax.devices() reports platform {dev['platform']!r}")
    require(dev["count"] == n_chips,
            f"expected {n_chips} chip(s), jax.devices() reports "
            f"{dev['count']}")

    from deepspeed_tpu.telemetry.record import detect_peak_flops_per_sec
    from deepspeed_tpu.utils.platform import setup_compile_cache

    jax.monitoring.register_event_listener(_count_cache_event)
    cache_dir = setup_compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    log(f"[device] platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"compile_cache={cache_dir} "
        f"peak_table={detect_peak_flops_per_sec() / 1e12:.0f} TFLOP/s")
    return dev


# ----------------------------------------------------------------------
# kernels: the Pallas kernels of both paths against their XLA references
# ----------------------------------------------------------------------
def kernels_phase(seed: int = 0) -> dict:
    """Run the flash and paged-decode kernels once at the widths the train
    and serve phases use and compare each with the repo's XLA path."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, supports)

    errs = {}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rnd(shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    # flash_attention takes [B, S, H, D]
    for name, (b, s, hq, hkv, d, window) in {
            "flash gpt2-350m": (2, 1024, 16, 16, 64, None),
            "flash mistral-7b": (1, 2048, 32, 8, 128, 4096)}.items():
        q, k, v = rnd((b, s, hq, d)), rnd((b, s, hkv, d)), rnd((b, s, hkv, d))
        errs[name] = max_err(
            flash_attention(q, k, v, impl="pallas", window=window),
            flash_attention(q, k, v, impl="xla", window=window))

    # paged decode at the serve model's head geometry: 8 tokens with
    # ragged contexts over a shared pool, bf16 pages and int8 pages
    cfg = get_model_config(SERVE_MODEL)
    nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    t, bs, nb, n_pages = 8, 16, 16, 64
    require(supports(bs, d), f"paged kernel refuses block {bs} head_dim {d}")
    q = rnd((t, nh, d))
    pool = {"k": rnd((nkv, n_pages * bs, d)), "v": rnd((nkv, n_pages * bs, d))}
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.integers(1, n_pages, size=(t, nb)), jnp.int32)
    clen = jnp.asarray(rng.integers(1, nb * bs + 1, size=(t,)), jnp.int32)
    pos = clen - 1
    c_idx = jnp.arange(nb * bs)
    gather_idx = tables[:, c_idx // bs] * bs + (c_idx % bs)[None, :]
    scale = 1.0 / float(np.sqrt(d))
    flat_cfg = cfg.replace(sliding_window=0)

    def quantized(x):
        xf = x.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
        return {"q": jnp.round(xf / s[..., None]).astype(jnp.int8), "s": s}

    for name, (kp, vp) in {
            "paged bf16": (pool["k"], pool["v"]),
            "paged int8": (quantized(pool["k"]), quantized(pool["v"]))}.items():
        ref = v2_model._paged_attention_xla(q, kp, vp, gather_idx, pos, clen,
                                            flat_cfg)
        if isinstance(kp, dict):
            out = paged_decode_attention(
                q, kp["q"], vp["q"], tables, pos, clen, bs, scale,
                k_scales=kp["s"], v_scales=vp["s"])
        else:
            out = paged_decode_attention(q, kp, vp, tables, pos, clen, bs,
                                         scale)
        errs[name] = max_err(out, ref)

    # a ragged step as the engine lays it out: block tables and a slot per
    # row; six decoding rows, then a 90-row prefill chunk on 100 cached
    # tokens (its rows share page walks), then padding (the last table row)
    n_dec, cached, chunk, t = 6, 100, 90, 128
    real = n_dec + chunk
    seq_tables = jnp.asarray(rng.integers(1, n_pages, size=(n_dec + 2, nb)),
                             jnp.int32)
    ctx = np.append(rng.integers(1, nb * bs, size=n_dec), [cached + chunk, 0])
    slot = np.full((t,), n_dec + 1, np.int32)
    slot[:real] = np.append(np.arange(n_dec), np.full(chunk, n_dec))
    pos = np.zeros((t,), np.int32)
    pos[:real] = np.append(ctx[:n_dec] - 1, np.arange(cached, cached + chunk))
    slot, pos = jnp.asarray(slot), jnp.asarray(pos)
    clen = jnp.asarray(ctx, jnp.int32)[slot]
    q = rnd((t, nh, d))
    gather_idx = (seq_tables[slot][:, c_idx // bs] * bs
                  + (c_idx % bs)[None, :])
    errs["paged bf16 ragged step"] = max_err(
        paged_decode_attention(q, pool["k"], pool["v"], seq_tables, pos, clen,
                               bs, scale, token_slot=slot)[:real],
        v2_model._paged_attention_xla(q, pool["k"], pool["v"], gather_idx,
                                      pos, clen, flat_cfg)[:real])

    for name, err in errs.items():
        log(f"[kernels] {name}: max |pallas - xla| = {err:.4f}")
        require(np.isfinite(err) and err < 0.05,
                f"{name}: Pallas kernel disagrees with XLA path ({err})")
    return errs


def ssd_phase(seed: int = 0, *, heads: int = 32, head_dim: int = 128,
              state: int = 256, groups: int = 2, slots: int = 8,
              chunk_rows: int = 150, chunk: int = 128) -> dict:
    """The state-space scan kernel at Falcon-H1-34B's widths against its
    XLA formulation over one ragged step: decode rows (runs of one), a
    prompt's first ``chunk_rows`` rows from position 0 in a slot that
    holds old state (crossing a chunk of the scan), a chunk that goes on
    from its slot, and padding.  One decode row is silent (x, B = 0): its
    slot must come back as exactly the decayed old state, which a state
    kept in anything narrower than float32 would not."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.ssd_ragged import ssd_ragged

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    runs = [(2, 40, 1), (0, 7, 1), (5, 3, 1), (1, 0, chunk_rows),
            (3, 64, 20)]                         # (slot, first position, rows)
    n_pad = 6
    slot = np.concatenate([np.full(n, s) for s, _, n in runs]
                          + [np.full(n_pad, slots)]).astype(np.int32)
    pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs]
                         + [np.zeros(n_pad)]).astype(np.int32)
    t = len(slot)
    bf16, f32 = jnp.bfloat16, jnp.float32
    x = jax.random.normal(next(keys), (t, heads, head_dim), bf16)
    b = jax.random.normal(next(keys), (t, groups, state), bf16)
    c = jax.random.normal(next(keys), (t, groups, state), bf16)
    x, b = x.at[1].set(0), b.at[1].set(0)        # the silent row, slot 0
    dt = jax.nn.softplus(jax.random.normal(next(keys), (t, heads), f32) - 2)
    a = -jnp.exp(jax.random.uniform(next(keys), (heads,), f32, 0.0, 2.7))
    old = jax.random.normal(next(keys), (slots + 1, heads, head_dim, state),
                            f32)
    args = (x, dt, a, b, c)
    want_y, want_s = ssd_ragged(*args, old, slot, pos, impl="xla")
    got_y, got_s = ssd_ragged(*args, old + 0, slot, pos, impl="pallas",
                              chunk=chunk)
    real = slot != slots
    scale_y = float(jnp.max(jnp.abs(want_y[real])))
    err_y = float(jnp.max(jnp.abs(got_y - want_y)[real])) / scale_y
    live = sorted({s for s, _, _ in runs})
    err_s = float(jnp.max(jnp.abs(got_s - want_s)[jnp.asarray(live)])
                  / jnp.max(jnp.abs(want_s[jnp.asarray(live)])))
    idle = [s for s in range(slots) if s not in live]
    untouched = bool(jnp.array_equal(got_s[jnp.asarray(idle)],
                                     old[jnp.asarray(idle)]))
    decayed = jnp.exp(dt[1] * a)[:, None, None] * old[0]
    err_silent = float(jnp.max(jnp.abs(got_s[0] - decayed))
                       / jnp.max(jnp.abs(decayed)))
    log(f"[ssd] {t} rows ({len(runs)} runs, {n_pad} padding), {heads} heads "
        f"of {head_dim}, state {state}: max |pallas - xla| y {err_y:.5f} of "
        f"max |y|, state {err_s:.5f} of max |state|; silent row's slot off "
        f"its decayed old state by {err_silent:.2e}; idle slots untouched "
        f"{untouched}")
    require(np.isfinite(err_y) and err_y < 0.02 and err_s < 0.02,
            f"ssd_ragged disagrees with its XLA formulation (y {err_y}, "
            f"state {err_s})")
    require(untouched, "ssd_ragged wrote a slot no run of the step names")
    require(err_silent < 1e-5, "ssd_ragged: a slot with no input is not its "
            f"decayed old state in float32 ({err_silent})")
    return {"y": err_y, "state": err_s, "silent": err_silent}


def append_phase(seed: int = 0, *, layers: int = 4, kv_heads: int = 8,
                 head_dim: int = 128, block_size: int = 16,
                 pages: int = 256, chunk_rows: int = 150) -> dict:
    """The page-granular KV append (both pools in one kernel) at
    mistral-7b's page shape against the row scatter it replaced, over one
    ragged step: a prompt's ``chunk_rows`` rows from the middle of a page,
    decode rows in pages of their own, padding.  Every page but page 0,
    the garbage page, must hold the same bits, and no other layer's page
    may change."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model import _kv_append
    from deepspeed_tpu.ops.pallas.kv_append import kv_append, step_pages

    bs = block_size
    rng = np.random.default_rng(seed)
    table = rng.permutation(np.arange(1, pages))
    pos = 5 + np.arange(chunk_rows)
    n_decode, n_pad = 9, 6
    dest = np.concatenate([
        table[pos // bs] * bs + pos % bs,
        table[-n_decode:] * bs + rng.integers(0, bs, size=n_decode),
        np.zeros(n_pad)]).astype(np.int32)
    t = len(dest)
    shape = (layers, kv_heads, pages * bs, head_dim)
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4))
    ck = jax.random.normal(next(keys), shape, bf16)
    cv = jax.random.normal(next(keys), shape, bf16)
    k = jax.random.normal(next(keys), (t, kv_heads, head_dim), bf16)
    v = jax.random.normal(next(keys), (t, kv_heads, head_dim), bf16)
    layer = jnp.int32(layers - 2)
    want = jax.jit(lambda ck, cv: (_kv_append(ck, k, dest, layer),
                                   _kv_append(cv, v, dest, layer)))(ck, cv)
    got = jax.jit(lambda ck, cv: kv_append(
        ck, cv, k, v, step_pages(ck, jnp.asarray(dest), bs), layer,
        bs))(ck, cv)
    same = all(bool(jnp.array_equal(g[:, :, bs:], w[:, :, bs:]))
               for g, w in zip(got, want))
    wrote = not bool(jnp.array_equal(got[0][layers - 2], ck[layers - 2]))
    log(f"[append] {t} rows ({chunk_rows} of a chunk, {n_decode} decode, "
        f"{n_pad} padding) into {kv_heads} x {pages} pages of {bs} x "
        f"{head_dim}: every page but page 0 equal to the scatter's {same}")
    require(same and wrote, "kv_append's pages are not the row scatter's")
    return {"same": same, "rows": t}


def sink_phase(seed: int = 0, *, heads: int = 64, head_dim: int = 192,
               value_dim: int = 128, window: int = 128,
               block_size: int = 128, blocks: int = 6, pages: int = 64,
               chunk_rows: int = 250, decode_rows: tuple = (13, 40)) -> dict:
    """``paged_qblock`` with K rows wider than V rows and a learned sink a
    head against the XLA gather path in float32, at mimo-v2-flash's two
    kinds of layer: 8 KV heads, a window and the sink; 4 KV heads, no
    window and none.  Three ragged steps over pools of several layers: six
    decoding rows (runs of ONE row), a chunk on cached tokens, padding;
    then ``decode_rows`` decoding rows alone in their token buckets, every
    context past the window: the steps whose every walk is multiplied on
    the narrow window of its program's tile (``narrow_rows``).
    The window kind's first step is also held against the XLA path given
    the sink with its SIGN flipped and with its heads in another order:
    each must read an order above the kernel's own distance, so a wrong
    sign or a wrong head-to-sink mapping on the chip cannot pass for
    rounding."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model as v2_model
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, row_width, supports)

    bs, nb = block_size, blocks
    require(supports(bs, head_dim, value_dim),
            f"paged kernel refuses block {bs} key {head_dim} value "
            f"{value_dim}")
    dk = row_width(head_dim)
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    bf16, f32 = jnp.bfloat16, jnp.float32
    cached = 2 * bs + 5
    require(cached + chunk_rows <= nb * bs, "the chunk passes its table")
    c_idx = jnp.arange(nb * bs)
    scale = 1.0 / float(np.sqrt(head_dim))
    base = get_model_config(SERVE_MODEL).replace(attn_scale=scale)

    def padded(x):              # a key row as its pool keeps it
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, dk - head_dim),))

    out = {}
    # decode rows, rows of a chunk behind them, the step's token bucket,
    # the name the step's distances are reported under
    for n_dec, n_chunk, t, tag in (
            (6, chunk_rows, -(-(6 + chunk_rows) // 128) * 128, ""),
            *((n, 0, max(16, 1 << (n - 1).bit_length()), f" {n} rows")
              for n in decode_rows)):
        real = n_dec + n_chunk
        tables = jnp.asarray(rng.integers(1, pages, size=(n_dec + 2, nb)),
                             jnp.int32)
        ctx = np.append(rng.integers(window + 1, nb * bs, size=n_dec),
                        [cached + n_chunk, 0])
        slot = np.full((t,), n_dec + 1, np.int32)
        slot[:real] = np.append(np.arange(n_dec), np.full(n_chunk, n_dec))
        pos = np.zeros((t,), np.int32)
        pos[:real] = np.append(ctx[:n_dec] - 1,
                               np.arange(cached, cached + n_chunk))
        slot, pos = jnp.asarray(slot), jnp.asarray(pos)
        clen = jnp.asarray(ctx, jnp.int32)[slot]
        gather_idx = tables[slot][:, c_idx // bs] * bs \
            + (c_idx % bs)[None, :]
        for kind, nkv, win, has_sink in (("window", 8, window, True),
                                         ("full", 4, None, False)):
            layers, layer = 3, 1
            q = padded(jax.random.normal(next(keys), (t, heads, head_dim),
                                         bf16))
            kp = padded(jax.random.normal(
                next(keys), (layers, nkv, pages * bs, head_dim), bf16))
            vp = jax.random.normal(next(keys), (layers, nkv, pages * bs,
                                                value_dim), bf16)
            # logits of 2 +- 2 beside 128 scores of about N(0, 1): a sink
            # holds from a hundredth to most of a row's probability, so its
            # sign and its head show far above rounding
            sink = 2.0 + 2.0 * jax.random.normal(next(keys), (heads,), f32) \
                if has_sink else None
            got = jax.jit(lambda q, kp, vp, sink: paged_decode_attention(
                q, kp, vp, tables, pos, clen, bs, scale, window=win,
                token_slot=slot, layer=jnp.int32(layer), sink=sink))(
                    q, kp, vp, sink)[:real].astype(f32)
            require(got.shape == (real, heads, value_dim),
                    f"{kind}: the kernel's output is {got.shape}")
            cfg = base.replace(sliding_window=win or 0)

            def xla(sink):
                return v2_model._paged_attention_xla(
                    q.astype(f32), kp[layer].astype(f32),
                    vp[layer].astype(f32), gather_idx, pos, clen, cfg,
                    sink)[:real]

            err = float(jnp.max(jnp.abs(got - xla(sink))))
            out[kind + tag] = err
            log(f"[sink] {kind} layer, {heads} heads to {nkv}, keys "
                f"{head_dim} in {dk} lanes, values {value_dim}, window "
                f"{win}, {n_dec} decode rows and a chunk of {n_chunk} in "
                f"{t}: max |pallas - xla float32| = {err:.5f}")
            require(np.isfinite(err) and err < 0.03,
                    f"{kind}{tag}: paged_qblock disagrees with the XLA "
                    f"path ({err})")
            if has_sink and not tag:
                for fault, wrong in (("sign", -sink),
                                     ("heads", jnp.roll(sink, 1))):
                    far = float(jnp.max(jnp.abs(got - xla(wrong))))
                    out[f"{kind} {fault}"] = far
                    log(f"[sink] against the XLA path with the sink's "
                        f"{fault} wrong: {far:.5f} "
                        f"({far / max(err, 1e-9):.0f} x)")
                    require(far > 10 * err,
                            f"a sink with its {fault} wrong reads {far}, "
                            f"the kernel as built {err}: the check cannot "
                            f"tell them apart")
    return out


def index_phase(seed: int = 0, *, heads: int = 64, dim: int = 128,
                block_size: int = 128, blocks: int = 8,
                chunk_rows: int = 150) -> dict:
    """The learned indexer's score kernel at dots3-note's widths against
    its XLA formulation (a gather of every row's keys) over one ragged
    step: decode rows of different sequences, a prompt's chunk that
    crosses a program's rows and several pages, a second chunk, and
    padding.  Also held: a key a row may not see reads ``-inf`` in both,
    so the sets the selection would choose are the same."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.latent import (index_scores_xla,
                                                   select_keys)
    from deepspeed_tpu.ops.pallas.latent_index import index_scores

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4))
    bs, nb = block_size, blocks
    ctx = bs * nb
    runs = [(2, ctx - 1, 1), (0, 3 * bs + 5, 1), (1, bs // 2 + 3, chunk_rows),
            (3, 0, 40)]                          # (slot, first position, rows)
    n_pad = 6
    slots = 4
    slot = np.concatenate([np.full(n, s) for s, _, n in runs]
                          + [np.full(n_pad, slots)]).astype(np.int32)
    pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs]
                         + [np.zeros(n_pad)]).astype(np.int32)
    t = len(slot)
    clen = np.zeros(slots + 1, np.int32)
    for s, p, n in runs:
        clen[s] = p + n
    tables = np.zeros((slots + 1, nb), np.int32)
    tables[:slots] = 1 + np.random.default_rng(seed).permutation(
        slots * nb).reshape(slots, nb)
    bf16 = jnp.bfloat16
    pool = jax.random.normal(next(keys), (2, (slots * nb + 1) * bs, dim), bf16)
    q = jax.random.normal(next(keys), (t, heads, dim), bf16)
    w = jax.random.normal(next(keys), (t, heads), jnp.float32) / heads
    args = (q, w, pool, jnp.int32(1), jnp.asarray(tables), jnp.asarray(slot),
            jnp.asarray(pos), jnp.asarray(clen)[slot])
    want = jax.jit(index_scores_xla, static_argnums=8)(*args, bs)
    got = index_scores(*args, block_size=bs)
    real = slot != slots
    same_mask = bool(jnp.array_equal(jnp.isinf(got)[real],
                                     jnp.isinf(want)[real]))
    seen = jnp.isfinite(want) & real[:, None]
    err = float(jnp.max(jnp.where(seen, jnp.abs(got - want), 0.0))
                / jnp.max(jnp.where(seen, jnp.abs(want), 0.0)))
    k = min(2048, ctx // 2)
    a, ok_a = select_keys(got, k)
    b, ok_b = select_keys(want, k)
    overlap = np.mean([
        len(set(np.asarray(a[i])[np.asarray(ok_a[i])])
            & set(np.asarray(b[i])[np.asarray(ok_b[i])]))
        / max(1, int(ok_b[i].sum())) for i in np.flatnonzero(real)[::7]])
    log(f"[index] {t} rows ({len(runs)} runs, {n_pad} padding), {heads} "
        f"heads of {dim}, {nb} pages of {bs}: max |pallas - xla| {err:.5f} "
        f"of max |score|; the same keys masked {same_mask}; the {k} best of "
        f"a row shared {overlap:.4f}")
    require(same_mask, "latent_index_scores masks other keys than its XLA "
            "formulation")
    require(np.isfinite(err) and err < 0.01 and overlap > 0.98,
            f"latent_index_scores disagrees with its XLA formulation "
            f"(scores {err}, chosen sets share {overlap})")
    return {"scores": err, "overlap": float(overlap)}


# ----------------------------------------------------------------------
# train
# ----------------------------------------------------------------------
def train_phase(model, *, micro_batch: int, gas: int, seq: int, steps: int,
                seed: int = 0, zero_stage: int = 1, mesh=None,
                global_rows=None, tag: str = "train") -> dict:
    """``ds.initialize`` + ``steps`` × ``engine.train_batch`` on ONE fixed
    batch made from ``seed``.  Returns the losses, per-step seconds, the
    compiled step's HLO text and where the parameters live."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.auditor import lower_step

    config = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero_stage},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "activation_checkpointing": {"remat_policy": "dots_flash_saveable"},
    }
    if mesh is not None:
        config["mesh"] = dict(mesh)
    engine, _, _, _ = ds.initialize(model=model, config=config, seed=seed)
    try:
        rows = micro_batch * gas * engine.topology.dp_size
        require(global_rows in (None, rows),
                f"{tag}: mesh {mesh} makes a {rows}-row batch, not "
                f"{global_rows}")
        ids = np.random.default_rng(seed).integers(
            0, model.vocab_size, size=(rows, seq + 1), dtype=np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:].copy()}

        t0 = time.perf_counter()
        fn, args = engine.audit_step_args(batch)
        hlo = lower_step(fn, *args, label=tag).hlo
        del fn, args
        compile_s = time.perf_counter() - t0
        losses, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            loss = jax.block_until_ready(engine.train_batch(batch))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(loss))
        param_devices = sorted({d.id for leaf in jax.tree.leaves(engine.params)
                                for d in leaf.sharding.device_set})
        in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in engine.topology.mesh.devices.flat}
        dev0 = engine.topology.mesh.devices.flat[0]
        axes = {a: n for a, n in engine.topology.sizes.items() if n > 1}
        log(f"[{tag}] mesh={axes or {'data': 1}} zero={zero_stage} "
            f"rows={rows} seq={seq} losses={[round(x, 4) for x in losses]}")
        log(f"[{tag}] compile {compile_s:.1f} s; step seconds "
            f"{[round(x, 3) for x in step_s]} (first includes dispatch "
            f"warm-up); {rows * seq / min(step_s):.0f} tokens/s best step "
            f"on {len(in_use)} x {dev0.device_kind}; {_hbm(dev0)}")
    finally:
        engine.destroy()
    require(all(np.isfinite(x) for x in losses), f"{tag}: non-finite loss")
    require(losses[-1] < losses[0],
            f"{tag}: loss did not fall on a fixed batch: {losses}")
    return {"losses": losses, "step_s": step_s, "compile_s": compile_s,
            "hlo": hlo, "param_devices": param_devices,
            "bytes_in_use": in_use}


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------
def _prompts(model, n: int, length: int, seed: int):
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(0, model.vocab_size, size=(length,)).tolist()
            for _ in range(n)]


def _compare_streams(eng, prompts, got, want, tag: str) -> int:
    """Hold greedy streams to their reference, token for token.

    With the same requests in a batch the two sides agree exactly.  With
    another batch composition the programs run at other shapes, their
    bf16 activations round differently, and an argmax over 32,000 nearly
    flat random-weight logits can flip on that.  So a stream may leave
    the reference at ONE place only: where the engine's own logits for
    the common prefix put both tokens within ``TIE_TOL`` of the top.  A
    wrong token is far outside that (a typical logit lies four standard
    deviations under the top).  Returns the number of such near-tie
    departures; anything else fails the run."""
    ties = 0
    for i, (g, w, p) in enumerate(zip(got, want, prompts)):
        require(len(g) == len(w), f"{tag}: request {i} returned {len(g)} "
                f"of {len(w)} tokens")
        if g == w:
            continue
        j = next(k for k, (a, b) in enumerate(zip(g, w)) if a != b)
        uid = 1 << 30                       # no live request uses it
        logits = np.asarray(eng.put([uid], [list(p) + w[:j]])[uid],
                            np.float32)
        eng.flush(uid)
        gap = float(logits.max() - min(logits[g[j]], logits[w[j]]))
        tol = TIE_TOL * float(np.abs(logits).max())
        log(f"[{tag}] request {i} leaves the reference at token {j}: "
            f"{g[j]} vs {w[j]}, {gap:.4f} under the top logit "
            f"(near-tie bound {tol:.4f}, logit std {logits.std():.3f})")
        require(gap <= tol, f"{tag}: request {i} token {j} is {g[j]}, the "
                f"reference has {w[j]}, and they are no near-tie "
                f"({gap:.4f} > {tol:.4f}): streamed {g}, reference {w}")
        ties += 1
    return ties


def serve_phase(model, engine_config: dict, *, n_requests: int,
                prompt_len: int, new_tokens: int, seed: int = 0) -> dict:
    """``InferenceEngineV2`` + ``InferenceServer``: submit, stream to the
    end, compare token for token with the engine's one-shot
    ``generate()``."""
    import jax

    from deepspeed_tpu.analysis.auditor import lower_step
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.serving import InferenceServer, SamplingParams

    t0 = time.perf_counter()
    eng = InferenceEngineV2(model, dict(engine_config), seed=seed)
    jax.block_until_ready(eng.params)
    init_s = time.perf_counter() - t0
    fn, args = eng.audit_step_args("decode")
    hlo = lower_step(fn, *args, label="serve_decode").hlo
    prompts = _prompts(model, n_requests, prompt_len, seed)

    t0 = time.perf_counter()
    want = eng.generate(prompts, max_new_tokens=new_tokens)
    first_s = time.perf_counter() - t0      # compiles every bucket
    t0 = time.perf_counter()
    again = eng.generate(prompts, max_new_tokens=new_tokens)
    gen_s = time.perf_counter() - t0
    require(again == want, "serve: generate() is not repeatable")

    # a burst: every request is queued when the serve loop starts, so its
    # first step batches them as generate() did and the tokens must agree
    srv = InferenceServer(eng, {})
    streams = [srv.submit(p, SamplingParams(max_new_tokens=new_tokens))
               for p in prompts]
    t0 = time.perf_counter()
    srv.start()
    try:
        got = [[int(tok) for tok in s] for s in streams]
        serve_s = time.perf_counter() - t0
    finally:
        srv.stop()
    dev = eng.topology.mesh.devices.flat[0]
    n_tok = n_requests * new_tokens
    log(f"[serve] {model.arch} layers={model.num_layers} "
        f"hidden={model.hidden_size} heads={model.num_heads}:"
        f"{model.kv_heads} head_dim={model.dim_per_head} "
        f"ffn={model.intermediate_size} window={model.sliding_window} "
        f"vocab={model.vocab_size} attention={eng.attention_impl}")
    log(f"[serve] init {init_s:.1f} s; first generate() (compiles) "
        f"{first_s:.1f} s; generate() {n_tok / gen_s:.1f} tokens/s; "
        f"served streams {n_tok / serve_s:.1f} tokens/s incl. its compiles, "
        f"on {dev.device_kind}; {_hbm(dev)}")
    ties = _compare_streams(eng, prompts, got, want, "serve")
    log(f"[serve] {n_requests} streamed requests x {new_tokens} tokens "
        f"equal generate() token for token"
        + (f" up to {ties} bf16 near-tie(s)" if ties else ""))
    return {"attention": eng.attention_impl, "hlo": hlo, "tokens": want,
            "ties": ties}


# ----------------------------------------------------------------------
# four chips: one sharded program, and replicas behind the router
# ----------------------------------------------------------------------
def replicas_phase(model, engine_config: dict, *, n_replicas: int,
                   n_requests: int, prompt_len: int, new_tokens: int,
                   seed: int = 0) -> dict:
    """``ReplicaSet.build`` + ``Router``: every replica on its own device,
    routed outputs equal to one replica's own ``generate()``."""
    import jax

    from deepspeed_tpu.serving import (ReplicaSet, Router, SamplingParams)

    rs = ReplicaSet.build(model, n_replicas, engine_config=dict(engine_config),
                          seed=seed, devices_per_replica=1)
    homes = []
    for rep in rs:
        arrays = jax.tree.leaves((rep.engine.params, rep.engine.cache_k,
                                  rep.engine.cache_v))
        homes.append(sorted({d.id for a in arrays
                             for d in a.sharding.device_set}))
    log(f"[replicas] devices per replica: {homes}")
    require(all(len(h) == 1 for h in homes)
            and len({h[0] for h in homes}) == n_replicas,
            f"replicas do not each own one device: {homes}")

    # One request at a time on each replica, in waves of n_replicas, held
    # to ONE replica's generate() of each prompt alone: the same batch
    # composition on both sides, so the tokens must agree exactly.
    prompts = _prompts(model, n_requests, prompt_len, seed)
    want = [rs[0].engine.generate([p], max_new_tokens=new_tokens)[0]
            for p in prompts]
    router = Router(rs).start()
    try:
        t0 = time.perf_counter()
        got = []
        for wave in range(0, n_requests, n_replicas):
            streams = [router.submit(p, SamplingParams(
                max_new_tokens=new_tokens))
                for p in prompts[wave:wave + n_replicas]]
            got += [[int(tok) for tok in s] for s in streams]
        routed_s = time.perf_counter() - t0
        served = {name: snap["tokens_out"] for name, snap
                  in rs.snapshot()["replicas"].items()}
    finally:
        router.stop()
    ties = _compare_streams(rs[0].engine, prompts, got, want, "replicas")
    log(f"[replicas] {n_requests} routed requests x {new_tokens} tokens "
        f"equal the single-replica tokens"
        + (f" up to {ties} bf16 near-tie(s)" if ties else "")
        + f"; tokens out per replica {served}; "
        f"{n_requests * new_tokens / routed_s:.1f} tokens/s incl. compiles")
    for rep in rs:
        log(f"[replicas] {rep.name}: "
            f"{_hbm(rep.engine.topology.mesh.devices.flat[0])}")
    require(sum(1 for n in served.values() if n) > 1,
            f"replicas: the router used one replica only: {served}")
    return {"homes": homes, "served": served, "ties": ties}


def sharded_train_phase(model, *, seq: int, steps: int, seed: int = 0) -> dict:
    """The same batch and seed on a one-device mesh and on data 2 x
    tensor 2 under ZeRO-3: losses agree, every device holds bytes."""
    one = train_phase(model, micro_batch=8, gas=2, seq=seq, steps=steps,
                      seed=seed, zero_stage=1, mesh={"data": 1},
                      global_rows=16, tag="train-1dev")
    _release()
    four = train_phase(model, micro_batch=4, gas=2, seq=seq, steps=steps,
                       seed=seed, zero_stage=3,
                       mesh={"data": 2, "tensor": 2}, global_rows=16,
                       tag="train-2x2")
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    log(f"[train-2x2] |loss(1 device) - loss(2x2 ZeRO-3)| per step: "
        f"{[round(x, 4) for x in diffs]}")
    require(max(diffs) <= MESH_LOSS_TOL,
            f"2x2 ZeRO-3 losses {four['losses']} leave the one-device "
            f"losses {one['losses']} by more than {MESH_LOSS_TOL}")
    require(len(four["param_devices"]) == 4,
            f"parameters live on devices {four['param_devices']}, not four")
    return {"one": one, "four": four}


def _serve_model():
    import jax.numpy as jnp

    from deepspeed_tpu.models import get_model_config

    # the engine keeps self-initialised weights in the config's
    # param_dtype (fp32 by default): ask for the serving dtype
    return get_model_config(SERVE_MODEL, num_layers=SERVE_LAYERS,
                            param_dtype=jnp.bfloat16)


def run_one_chip(seed: int) -> None:
    from deepspeed_tpu.models import get_model_config

    kernels_phase(seed)
    ssd_phase(seed)
    append_phase(seed)
    sink_phase(seed)
    index_phase(seed)
    train = train_phase(
        get_model_config(TRAIN_MODEL, max_seq_len=TRAIN_SEQ),
        micro_batch=TRAIN_MICRO_BATCH, gas=TRAIN_GAS, seq=TRAIN_SEQ,
        steps=TRAIN_STEPS, seed=seed)
    n_kernels = train["hlo"].count("tpu_custom_call")
    log(f"[train] Pallas kernels in the compiled step: {n_kernels} "
        f"tpu_custom_call")
    require(n_kernels > 0, "train: the compiled step has no Pallas flash "
            "kernel (XLA attention branch taken)")
    log(f"[train] compile cache so far: {_cache_events}")
    del train
    _release()

    serve = serve_phase(_serve_model(), SERVE_ENGINE,
                        n_requests=SERVE_REQUESTS,
                        prompt_len=SERVE_PROMPT_LEN,
                        new_tokens=SERVE_NEW_TOKENS, seed=seed)
    require(serve["attention"] == "paged_pallas",
            f"serve: registry chose {serve['attention']}, not paged_pallas")
    require("tpu_custom_call" in serve["hlo"],
            "serve: the compiled decode step has no Pallas kernel")


def run_four_chips(seed: int) -> None:
    from deepspeed_tpu.models import get_model_config

    both = sharded_train_phase(
        get_model_config(TRAIN_MODEL, max_seq_len=TRAIN_SEQ),
        seq=TRAIN_SEQ, steps=3, seed=seed)
    hlo = both["four"]["hlo"]
    found = {op: hlo.count(op) for op in ("all-gather", "reduce-scatter",
                                          "all-reduce", "tpu_custom_call")}
    log(f"[train-2x2] in the compiled step: {found}")
    require(found["all-gather"] and found["reduce-scatter"],
            f"2x2 ZeRO-3 step has no all-gather/reduce-scatter: {found}")
    in_use = both["four"]["bytes_in_use"]
    log(f"[train-2x2] bytes in use per device: {in_use}")
    require(len(in_use) == 4 and all(n > 0 for n in in_use.values()),
            f"a device holds no bytes: {in_use}")
    del both, hlo
    _release()

    replicas_phase(_serve_model(), SERVE_ENGINE, n_replicas=4, n_requests=8,
                   prompt_len=SERVE_PROMPT_LEN, new_tokens=SERVE_NEW_TOKENS,
                   seed=seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip path (sharded train, routed "
                         "replicas) and what it is compared with, only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        dev = device_phase(4 if args.four_chips else 1)
        (run_four_chips if args.four_chips else run_one_chip)(args.seed)
    except SmokeFailure as e:
        log(f"[FAIL] {e}")
        return 1
    log(f"[done] {time.perf_counter() - t0:.0f} s; compile cache "
        f"{_cache_events}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
