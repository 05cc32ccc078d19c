"""Fused ZeRO-3 gather-matmul: the matmul whose epilogue region issues
the NEXT matmul's parameter all-gather.

The T3 move (arXiv:2401.16677) applied to the stage-3 forward: instead
of leaving the per-use parameter all-gathers to GSPMD's scheduling
(which the ``step_schedule.zero3_prefetch`` arm can only *hoist* by
widening the layer-scan unroll window), the layer MLP runs inside an
explicit ``shard_map`` over the ZeRO axes where

* every weight shard is gathered by an EXPLICIT ``lax.all_gather``
  issued at the top of the fused region — the SECOND matmul's gather
  (and the swiglu gate branch's) is emitted before the first matmul
  runs, so it is dataflow-independent of that matmul and the
  latency-hiding scheduler overlaps transfer with MXU work; and
* the matmuls themselves run as ONE blocked Pallas kernel each
  (``matmul_block``) on TPU — a single opaque custom call the compiler
  cannot split or re-order around the in-flight gather, which pins the
  overlap window the fusion creates (off-TPU the same contraction runs
  as a jnp dot behind the same gate, so CPU parity tests cover the
  wiring).

Composition: ``step_schedule.gather_prefetch_depth`` still unrolls the
layer scan, so consecutive unrolled layer bodies expose *their* fused
regions' gathers to each other — layer i+1's gather issues under layer
i's matmuls.  The overlap scheduler's decision table picks this fused
arm vs the scheduled (unroll-only) arm from the same probe evidence
(``fused_gather_matmul`` decision, docs/AUTOTUNING.md).

The engine enables the path only after verifying the MLP weights carry
the expected fsdp sharding pattern (wi/wg sharded on the embed dim 0,
wo on the embed dim 1) — see ``runtime/engine.py``; anything else
warn-falls back to GSPMD scheduling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.parallel.topology import BATCH_AXES
from deepspeed_tpu.utils.jax_compat import (all_axes_manual,
                                            get_abstract_mesh, shard_map,
                                            with_unit_axes)
from deepspeed_tpu.utils.platform import on_tpu

# Set True (tests) to run the matmul kernel through the Pallas
# interpreter so the fused path is checkable on the CPU mesh.
INTERPRET = False

# Block edges: [bm, bk] x [bk, bn] fp32 accumulation in VMEM scratch.
# 256³ keeps the per-program footprint (two input tiles + fp32 acc,
# double-buffered) well inside scoped VMEM for bf16/f32 operands.
_BLK_M = 256
_BLK_N = 256
_BLK_K = 256


def _kernel_enabled() -> bool:
    """Run the Pallas matmul: under the interpreter flag (CPU parity
    tests), and on TPU where every mesh axis is manual at this point of
    the trace — with an axis left to GSPMD the chip's compiler refuses
    the kernel and ``jnp.dot`` runs instead."""
    return INTERPRET or (on_tpu() and all_axes_manual())


def _matmul_kernel(x_ref, w_ref, o_ref, acc_scr):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _pad_to(x, m, axis):
    s = x.shape[axis]
    if s % m == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - s % m)
    return jnp.pad(x, pad)


@jax.custom_vjp
def pallas_matmul(x, w):
    """Blocked Pallas matmul ``[M, K] @ [K, N] -> [M, N]`` (fp32 VMEM
    accumulation, zero-padding to block multiples, result in ``x``'s
    dtype).  Falls back to ``jnp.dot`` when the kernel gate is off.
    Differentiable: the hand-written VJP runs the transposed
    contractions through the same kernel (``pallas_call`` has no AD
    rule of its own)."""
    return _matmul_impl(x, w)


def _mm_fwd(x, w):
    return _matmul_impl(x, w), (x, w)


def _mm_bwd(res, g):
    x, w = res
    dx = _matmul_impl(g, w.T)            # [M, N] @ [N, K]
    dw = _matmul_impl(x.T, g)            # [K, M] @ [M, N]
    return dx.astype(x.dtype), dw.astype(w.dtype)


def _matmul_impl(x, w):
    if not _kernel_enabled():
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
    m, k = x.shape
    n = w.shape[1]
    bm = min(_BLK_M, -(-m // 8) * 8)
    bn = min(_BLK_N, -(-n // 128) * 128)
    bk = min(_BLK_K, -(-k // 128) * 128)
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        interpret=INTERPRET,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((bk, bn), lambda im, jn, ik: (ik, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(xp, wp)
    return out[:m, :n]


pallas_matmul.defvjp(_mm_fwd, _mm_bwd)


def gather_matmul(x, w_shard, axes, shard_dim, *, prefetch=()):
    """One fused gather-matmul INSIDE a manual (shard_map) region:
    all-gather ``w_shard`` over ``axes`` (tiled on ``shard_dim``), run
    the Pallas matmul against the gathered weight, and ALSO issue the
    all-gathers for every ``(shard, dim)`` in ``prefetch`` FIRST — those
    are the following matmuls' parameters, emitted in this matmul's
    epilogue region so their transfer overlaps this matmul's compute.

    Returns ``(y, gathered_prefetch_tuple)``."""
    nexts = tuple(lax.all_gather(s, axes, axis=d, tiled=True)
                  for s, d in prefetch)
    w = lax.all_gather(w_shard, axes, axis=shard_dim, tiled=True)
    lead = x.shape[:-1]
    y = pallas_matmul(x.reshape(-1, x.shape[-1]), w)
    return y.reshape(lead + (w.shape[1],)), nexts


def fused_gather_mlp(x, p, cfg):
    """The transformer MLP on the fused gather-matmul path
    (``step_schedule.fused_gather_matmul``; called from
    models/transformer.py ``_mlp_block`` when the engine enabled the
    flag).  ``x [B, S, H]`` batch-sharded, ``p`` the layer's mlp params
    with wi/wg sharded on dim 0 and wo on dim 1 over
    ``cfg.fused_gather_axes``.  Biases (when present) stay outside the
    manual region — they are small and GSPMD's implicit gather of them
    is already declared intent."""
    axes = tuple(cfg.fused_gather_axes)
    ctx = get_abstract_mesh()
    if ctx.empty:  # pragma: no cover - engine always jits under the mesh
        from deepspeed_tpu.parallel.topology import get_topology

        mesh = get_topology().mesh
    else:
        mesh = ctx
    swiglu = cfg.activation == "swiglu"
    P = jax.sharding.PartitionSpec
    dt = x.dtype
    ax = axes if len(axes) > 1 else axes[0]
    bi = p.get("bi")
    has_bi = bi is not None and not swiglu

    def local(x_l, wi_l, wo_l, wg_l, bi_l):
        # the SECOND matmul's gather (and the gate branch's, and the
        # tiny pre-activation bias') issues before the first matmul runs
        # — dataflow-independent, so the scheduler overlaps the
        # transfers with the MXU work below
        pre = ((wo_l, 1), (wg_l, 0), (bi_l, 0))
        h, (wo_full, wg_full, bi_full) = gather_matmul(
            x_l, wi_l, axes, 0, prefetch=pre)
        if has_bi:
            h = h + bi_full
        if swiglu:
            lead = x_l.shape[:-1]
            gate = pallas_matmul(x_l.reshape(-1, x_l.shape[-1]), wg_full)
            h = jax.nn.silu(gate.reshape(lead + (wg_full.shape[1],))) * h
        else:
            h = jax.nn.relu(h) if cfg.activation == "relu" \
                else jax.nn.gelu(h, approximate=cfg.activation != "gelu_exact")
        y = pallas_matmul(h.reshape(-1, h.shape[-1]), wo_full)
        return y.reshape(x_l.shape[:-1] + (wo_full.shape[1],))

    xspec = P(BATCH_AXES, None, None)
    wi_spec = P(ax, None)
    wo_spec = P(None, ax)
    wg = p.get("wg") if swiglu else None
    if wg is None:
        # keep the shard_map arity fixed: zero-size dummies ride the
        # unused slots (never touched in the body)
        wg = jnp.zeros((p["wi"].shape[0], 0), dt)
    bi_in = bi if has_bi else jnp.zeros((0,), dt)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(xspec, wi_spec, wo_spec, wi_spec, P(ax)),
                   out_specs=xspec,
                   axis_names=with_unit_axes(mesh, {*BATCH_AXES, *axes}),
                   check_vma=False)
    return fn(x, p["wi"].astype(dt), p["wo"].astype(dt), wg.astype(dt),
              bi_in.astype(dt))
