"""On-chip micro-benchmark of the Pallas flash attention kernels at long
sequence (the KV-blocked path): fwd and fwd+bwd achieved TFLOP/s vs the
causal-attention flop count.  Quantifies kernel-level MFU separately from
the end-to-end longseq bench row (which folds in dense matmuls + remat).
Not part of the suite."""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--smoke" in sys.argv:
    # CPU plumbing check: JAX reads the platform from the environment
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import jax
import jax.numpy as jnp


ITERS = 8


def timeit(f, *args):
    """f must iterate ITERS times inside one jit AND reduce to a scalar
    (per-call dispatch and a full-array fetch would each swamp the
    kernel)."""
    r = f(*args)
    assert getattr(r, "ndim", 0) == 0, "bench fns must reduce to a scalar"
    float(np.asarray(r))
    t0 = time.perf_counter()
    float(np.asarray(f(*args)))
    return (time.perf_counter() - t0) / ITERS


def attn_flops(b, h, s, d, causal=True):
    # scores + pv matmuls: 2 * 2 * B*H*S^2*D, halved by causal skipping
    f = 4 * b * h * s * s * d
    return f / 2 if causal else f


def ring_sweep(fm, smoke: bool):
    """The queued `_RING_BLK` 512-vs-1024 sweep (ROADMAP item 2 /
    BENCH_MEASURED r06-r07): time one ring hop — a fused
    ``flash_carry_block`` online-softmax update of the (m, l, acc) carry
    against a visiting K/V block — at per-shard S_l >= 4k, d=128 GQA
    geometry, per candidate block edge.  ``--smoke`` runs a tiny shape
    through the Pallas interpreter (plumbing check only, no numbers of
    record); on-chip: ``python tools/bench_flash_longseq.py --sweep``."""
    if smoke:
        fm.INTERPRET = True
        cases = [(1, 4, 2, 256, 64)]       # b, hq, hkv, S_l, d
        blocks = [128, 256]
        hops = 2
    else:
        cases = [(1, 16, 8, 4096, 128), (1, 16, 8, 8192, 128)]
        blocks = [512, 1024]
        hops = ITERS
    neg = float(np.finfo(np.float32).min)
    for (b, hq, hkv, s_l, d) in cases:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, hq, s_l, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, hkv, s_l, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, hkv, s_l, d)), jnp.bfloat16)
        for blk in blocks:
            prev = fm._RING_BLK
            fm._RING_BLK = blk
            try:
                s_pad = fm.ring_carry_pad(s_l)
                pad = lambda x: jnp.pad(  # noqa: E731
                    x, ((0, 0), (0, 0), (0, s_pad - s_l), (0, 0)))
                qp, kp, vp = pad(q), pad(k), pad(v)

                @jax.jit
                def one(qp, kp, vp):
                    m0 = jnp.full((b, hq, s_pad, 128), neg, jnp.float32)
                    l0 = jnp.zeros((b, hq, s_pad, 128), jnp.float32)
                    a0 = jnp.zeros((b, hq, s_pad, d), jnp.float32)

                    def hop(carry, src):
                        m, l, acc = carry
                        m, l, acc = fm.flash_carry_block(
                            qp, kp, vp, m, l, acc,
                            jnp.int32((hops - 1) * s_l),  # causally live q
                            src * s_l, s_real=s_l, causal=True)
                        return (m, l, acc), None

                    (m, l, acc), _ = jax.lax.scan(
                        hop, (m0, l0, a0),
                        jnp.arange(hops, dtype=jnp.int32))
                    return jnp.sum(acc) + jnp.sum(l[..., :1]) \
                        + jnp.sum(m[..., :1])

                t = timeit(one, qp, kp, vp) / max(1, hops) * ITERS
            except Exception as e:
                print(f"ring S_l={s_l} d={d} blk={blk}: FAILED "
                      f"{str(e)[:200]}", flush=True)
                fm._RING_BLK = prev
                continue
            fm._RING_BLK = prev
            fl = attn_flops(b, hq, s_l, d, causal=False)  # one full hop
            print(f"ring S_l={s_l} d={d} hq:hkv={hq}:{hkv} blk={blk}: "
                  f"{t*1e3:.2f} ms/hop = {fl/t/1e12:.1f} TF/s "
                  f"({fl/t/197e12:.1%})", flush=True)


def bwd_sweep(fm, smoke: bool):
    """--bwd: per-hop ring BACKWARD timing (ROADMAP item 2 acceptance) —
    the fused offset-aware dq/dkv flash kernels vs the XLA einsum hop of
    the ``sequence/ring.py`` fallback, on the same fully-live causal hop,
    plus an estimated peak per-hop transient-bytes figure for each path:
    SCORE-shaped for the einsums (s/p/dp/ds fp32, 4·S_l²·hkv·rep·4 B) vs
    BLOCK-shaped for the kernels (≈4 fp32 [bq, bk] tiles per program,
    grid-sequential so they never coexist across programs).  One JSON row
    per case with the frozen keys linted by tools/telemetry_check.py
    ``RING_BWD_BENCH_KEYS``.  ``--bwd --smoke`` runs a tiny shape through
    the Pallas interpreter and asserts the fused estimate really is
    block-shaped; on-chip: ``python tools/bench_flash_longseq.py --bwd``."""
    if smoke:
        fm.INTERPRET = True
        cases = [(1, 4, 2, 256, 64)]       # b, hq, hkv, S_l, d
        hops = 2
    else:
        cases = [(1, 16, 8, 4096, 128), (1, 16, 8, 8192, 128)]
        hops = ITERS
    for (b, hq, hkv, s_l, d) in cases:
        rep = hq // hkv
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, hq, s_l, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, hkv, s_l, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, hkv, s_l, d)), jnp.bfloat16)
        do = jnp.asarray(rng.standard_normal((b, hq, s_l, d)), jnp.bfloat16)
        s_pad = fm.ring_carry_pad(s_l)
        assert s_pad == s_l, "bench cases are block-aligned"
        # q one block AHEAD of the visiting K/V block: every tile of the
        # causal hop is live — the worst-case (dense) per-hop cost
        q_off, k_off = jnp.int32(s_l), jnp.int32(0)
        neg = float(np.finfo(np.float32).min)

        # forward residuals the backward consumes: one carry hop -> o, lse
        m0 = jnp.full((b, hq, s_l, 128), neg, jnp.float32)
        l0 = jnp.zeros((b, hq, s_l, 128), jnp.float32)
        a0 = jnp.zeros((b, hq, s_l, d), jnp.float32)
        m, l, acc = jax.jit(fm.flash_carry_block, static_argnames=(
            "q_stride", "k_stride", "s_real", "sm_scale", "causal",
            "window"))(q, k, v, m0, l0, a0, q_off, k_off, s_real=s_l,
                       causal=True)
        l1 = jnp.maximum(l[..., 0], 1e-20)
        o = (acc / l1[..., None]).astype(q.dtype)
        lse = m[..., 0] + jnp.log(l1)
        lsep, deltap = fm.bwd_lane_residuals(o, do, lse, s_l)

        @jax.jit
        def fused(q, k, v, do, lsep, deltap):
            dq0 = jnp.zeros((b, hq, s_l, d), jnp.float32)
            dk0 = jnp.zeros((b, hkv, s_l, d), jnp.float32)
            dv0 = jnp.zeros((b, hkv, s_l, d), jnp.float32)

            def hop(carry, _):
                dq, dk, dv = carry
                dq = fm.flash_ring_dq_block(
                    q, k, v, do, lsep, deltap, dq, q_off, k_off,
                    s_real=s_l, causal=True)
                dk, dv = fm.flash_ring_dkv_block(
                    q, k, v, do, lsep, deltap, dk, dv, q_off, k_off,
                    s_real=s_l, causal=True)
                return (dq, dk, dv), None

            (dq, dk, dv), _ = jax.lax.scan(
                hop, (dq0, dk0, dv0), None, length=hops)
            return jnp.sum(dq) + jnp.sum(dk) + jnp.sum(dv)

        @jax.jit
        def xla(q, k, v, do, lse, o):
            # the einsum hop of sequence/ring.py _ring_bwd_xla, dense
            q5 = q.astype(jnp.float32).reshape(b, hkv, rep, s_l, d)
            do5 = do.astype(jnp.float32).reshape(b, hkv, rep, s_l, d)
            o5 = o.astype(jnp.float32).reshape(b, hkv, rep, s_l, d)
            delta = jnp.sum(do5 * o5, -1)[..., None]
            lse_ = lse.reshape(b, hkv, rep, s_l)[..., None]
            kf = k.astype(jnp.float32).swapaxes(1, 2)     # [b, s, c, d]
            vf = v.astype(jnp.float32).swapaxes(1, 2)
            scale = 1.0 / np.sqrt(d)

            def hop(carry, _):
                dq, dk, dv = carry
                s = jnp.einsum("bcgqd,bscd->bcgqs", q5, kf) * scale
                p = jnp.exp(s - lse_)
                dv_c = jnp.einsum("bcgqs,bcgqd->bscd", p, do5)
                dp = jnp.einsum("bcgqd,bscd->bcgqs", do5, vf)
                ds = p * (dp - delta) * scale
                dq_c = jnp.einsum("bcgqs,bscd->bcgqd", ds, kf)
                dk_c = jnp.einsum("bcgqs,bcgqd->bscd", ds, q5)
                return (dq + dq_c, dk + dk_c, dv + dv_c), None

            z_q = jnp.zeros((b, hkv, rep, s_l, d), jnp.float32)
            z_kv = jnp.zeros((b, s_l, hkv, d), jnp.float32)
            (dq, dk, dv), _ = jax.lax.scan(
                hop, (z_q, z_kv, z_kv), None, length=hops)
            return jnp.sum(dq) + jnp.sum(dk) + jnp.sum(dv)

        try:
            t_f = timeit(fused, q, k, v, do, lsep, deltap) \
                / max(1, hops) * ITERS
            t_x = timeit(xla, q, k, v, do, lse, o) / max(1, hops) * ITERS
        except Exception as e:
            print(f"ring bwd S_l={s_l} d={d}: FAILED {str(e)[:200]}",
                  flush=True)
            continue
        # peak fused transient = the LARGER of the two kernels' tile
        # geometries: dq tiles at the full ring edge, the grouped dkv
        # halves its q-edge under GQA (_ring_bwd_blocks)
        bq_dkv, bk = fm._ring_bwd_blocks(s_l, rep)
        bk_dq = min(fm._RING_BLK, s_l)
        bytes_fused = 4 * max(bk_dq * bk_dq, bq_dkv * bk) * 4
        bytes_xla = 4 * b * s_l * s_l * hkv * rep * 4
        row = {
            "metric": f"ring_bwd_hop_S{s_l}_d{d}_gqa{hq}:{hkv}",
            "bwd_ms_per_hop_fused": round(t_f * 1e3, 3),
            "bwd_ms_per_hop_xla": round(t_x * 1e3, 3),
            "transient_bytes_fused": bytes_fused,
            "transient_bytes_xla": bytes_xla,
            "transient_reduction": round(bytes_xla / bytes_fused, 1),
        }
        assert bytes_fused < bytes_xla, row  # block-shaped, not score-
        print(json.dumps(row), flush=True)


def main():
    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    # the package re-exports the flash_mha FUNCTION over the submodule
    # name — import the module itself for the _BLK_* knobs
    import importlib

    fm = importlib.import_module("deepspeed_tpu.ops.pallas.flash_mha")

    sweep = "--sweep" in sys.argv
    smoke = "--smoke" in sys.argv
    if "--bwd" in sys.argv:
        # backward-hop mode: fused dq/dkv kernels vs the XLA einsum hop
        bwd_sweep(fm, smoke=smoke)
        return
    if sweep and smoke:
        # CPU plumbing check of the ring sweep only (the MHA sweep below
        # needs a real chip; interpreted 32k shapes would run for hours)
        ring_sweep(fm, smoke=True)
        return
    if sweep:
        ring_sweep(fm, smoke=False)
    blocks = [(None, None)]  # None → the shipped _choose_blocks heuristic
    if sweep:
        blocks = [(None, None), (512, 512), (512, 1024), (1024, 512),
                  (256, 1024), (1024, 1024), (256, 512)]
    for (b, h, s, d) in [(1, 16, 32768, 64), (1, 8, 32768, 128),
                         (1, 16, 8192, 64)]:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
        fl = attn_flops(b, h, s, d)
        for bq, bk in blocks:
            fm._BLK_Q, fm._BLK_K = bq, bk
            try:
                from jax import lax

                @jax.jit
                def fwd(q, k, v):
                    def body(c, _):
                        return fm.flash_mha(c, k, v, True), ()

                    out, _ = lax.scan(body, q, None, length=ITERS)
                    return jnp.sum(out.astype(jnp.float32))

                t_f = timeit(fwd, q, k, v)
                gfn = jax.grad(lambda q, k, v: fm.flash_mha(
                    q, k, v, True).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))

                @jax.jit
                def grad(q, k, v):
                    # dk/dv must stay LIVE via the carry or XLA dead-code
                    # eliminates the dkv kernel and "fwd+bwd" times only
                    # fwd+dq (r04 review finding)
                    def body(carry, _):
                        c, acc = carry
                        dq, dk, dv = gfn(c, k, v)
                        acc = acc + jnp.sum(dk.astype(jnp.float32)) \
                            + jnp.sum(dv.astype(jnp.float32))
                        return (c - 1e-3 * dq.astype(c.dtype), acc), ()

                    (out, acc), _ = lax.scan(
                        body, (q, jnp.float32(0.0)), None, length=ITERS)
                    return jnp.sum(out.astype(jnp.float32)) + acc

                t_g = timeit(grad, q, k, v)
            except Exception as e:
                lab = "auto" if bq is None else f"({bq},{bk})"
                print(f"S={s} D={d} H={h} blk={lab}: FAILED "
                      f"{str(e)[:200]}")
                continue
            fl_g = fl * 3.5  # bwd ≈ 2.5x fwd (dq + dkv recompute scores)
            lab = "auto" if bq is None else f"({bq},{bk})"
            print(f"S={s} D={d} H={h} blk={lab}: "
                  f"fwd {t_f*1e3:.2f} ms = {fl/t_f/1e12:.1f} TF/s "
                  f"({fl/t_f/197e12:.1%}); fwd+bwd {t_g*1e3:.2f} ms "
                  f"= {fl_g/t_g/1e12:.1f} TF/s ({fl_g/t_g/197e12:.1%})",
                  flush=True)
        fm._BLK_Q = fm._BLK_K = None


if __name__ == "__main__":
    print(f"devices: {jax.devices()}")
    main()
