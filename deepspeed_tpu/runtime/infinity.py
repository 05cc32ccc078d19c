"""ZeRO-Infinity parameter streaming: train models whose parameters exceed
HBM by keeping the stacked layer weights in host memory (optionally backed
by NVMe via the AIO engine) and streaming one layer at a time through the
compiled step.

TPU-native re-design of the reference's ``AsyncPartitionedParameterSwapper``
(``deepspeed/runtime/swap_tensor/partitioned_param_swapper.py:37``) and the
ZeRO-3 gather/release hooks (``runtime/zero/parameter_offload.py:246``): the
reference swaps each parameter in around its module's forward with explicit
CUDA streams; here the swap schedule is *compiled* — every fetch is a
``dynamic_slice`` of a ``pinned_host`` buffer followed by an H2D copy that
XLA's latency-hiding scheduler overlaps with the previous layer's compute
(raise ``scan_unroll`` to widen the overlap window).

The hard part is the backward: naive AD would accumulate the parameter
cotangent as a full-size device buffer, defeating the offload (measured:
full param bytes reappear as XLA temp).  :func:`streamed_scan` therefore
carries a custom VJP whose backward walks the layers in reverse,
re-linearizing one layer at a time (``jax.vjp``) from an activation stash
and writing each layer's gradient straight back into a host-resident
accumulator — device residency stays O(one layer) in both directions.

The same slice-wise pattern covers the other full-size trees:
:func:`streamed_tree_add` (gradient accumulation across micro-batches) and
:func:`streamed_update` (the optimizer step, ref
``partitioned_optimizer_swapper.py:27``) loop over the layer axis with
host-resident operands.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.utils.jax_compat import memory_spaces
from deepspeed_tpu.utils.platform import on_tpu

HOST, DEVICE = memory_spaces()

_MEMORY_KINDS: dict = {}


def memory_kinds_supported() -> bool:
    """Whether this backend executes host-space placement. Real TPUs: yes.
    The CPU test mesh: no — it *compiles* small probe programs (XLA folds
    the placement annotations away) but aborts at runtime when an
    `annotate_device_placement` custom call survives into a real program,
    so behavioral probing is unreliable and the decision is by platform.
    When False every placement below is an identity and the streaming code
    paths run against unified memory (numerics still fully testable)."""
    plat = jax.devices()[0].platform
    if plat not in _MEMORY_KINDS:
        if not on_tpu():
            _MEMORY_KINDS[plat] = False
        else:
            try:
                # Probe the exact patterns streaming uses: a host-space
                # DUS accumulation in a scan carry (park_slice) and a
                # host-arg slice read (fetch_slice).  BOTH DUS operands
                # must be host-placed — libtpu's host offloader rejects a
                # device-resident update operand, and a probe written that
                # way reads as "unsupported" on runtimes where the real
                # pattern is fine (r04: that false negative silently
                # degraded every Infinity placement to device and OOM'd
                # the 6.7B streaming ladder entry).
                def probe(w):
                    z = jax.device_put(jnp.zeros(w.shape, w.dtype), HOST)

                    def body(c, i):
                        u = jax.device_put(
                            lax.dynamic_index_in_dim(w, i, keepdims=False)
                            * 2.0, HOST)
                        return lax.dynamic_update_index_in_dim(
                            c, u, i, axis=0), None

                    out, _ = lax.scan(body, z, jnp.arange(w.shape[0]))
                    return out

                jax.jit(probe)(jnp.ones((2, 256)))[0].block_until_ready()
                _MEMORY_KINDS[plat] = True
            except Exception:
                _MEMORY_KINDS[plat] = False
    return _MEMORY_KINDS[plat]


def _put(x, space):
    return jax.device_put(x, space) if memory_kinds_supported() else x


def split_layers(tree):
    """Split an engine param-style dict into (layers, resident) partitions."""
    return tree["layers"], {k: v for k, v in tree.items() if k != "layers"}


def to_host(tree):
    """Place a pytree in host memory (inside or outside jit)."""
    return jax.tree.map(lambda x: _put(x, HOST), tree)


def to_device(tree):
    return jax.tree.map(lambda x: _put(x, DEVICE), tree)


def fetch_slice(stacked_host, i):
    """Layer ``i`` of a host-resident stacked tree → device."""
    return jax.tree.map(
        lambda p: _put(lax.dynamic_index_in_dim(p, i, keepdims=False),
                       DEVICE),
        stacked_host)


def park_slice(acc_host, sl, i):
    """Write a device slice into row ``i`` of a host-resident stacked tree
    (dynamic-update-slice on the host buffer — the D2H path).  Both DUS
    operands are normalised to host space (no-ops when already there)."""
    return jax.tree.map(
        lambda a, s: lax.dynamic_update_index_in_dim(
            _put(a, HOST), _put(s.astype(a.dtype), HOST), i, axis=0),
        acc_host, sl)


def streamed_scan(step_fn: Callable, stacked_host, h0, extras=()):
    """``h, aux = step_fn(layer_params, h, i)`` scanned over the leading
    layer axis of ``stacked_host`` (host-resident), with O(1-layer) device
    parameter residency in forward AND backward.

    Returns ``(h_final, aux_sum, grad_fn_residual-free loss path)`` —
    concretely ``(h, aux)`` with a custom VJP: the backward re-fetches each
    layer, re-linearizes it from the stashed layer *inputs* (activation
    checkpointing at layer granularity), and parks each ``d(layer_params)``
    into a host accumulator slice, so the full parameter gradient never
    exists in device memory.
    """
    steps = jax.tree.leaves(stacked_host)[0].shape[0]

    @jax.custom_vjp
    def run(stacked_host, h0, extras):
        def body(carry, i):
            h, aux = carry
            lp = fetch_slice(stacked_host, i)
            h, a = step_fn(lp, h, extras, i)
            return (h, aux + a.astype(jnp.float32)), None

        (h, aux), _ = lax.scan(body, (h0, jnp.zeros((), jnp.float32)),
                               jnp.arange(steps))
        return h, aux

    def run_fwd(stacked_host, h0, extras):
        def body(carry, i):
            h, aux = carry
            lp = fetch_slice(stacked_host, i)
            h2, a = step_fn(lp, h, extras, i)
            return (h2, aux + a.astype(jnp.float32)), h

        (h, aux), h_stash = lax.scan(
            body, (h0, jnp.zeros((), jnp.float32)), jnp.arange(steps))
        return (h, aux), (stacked_host, h_stash, extras)

    def run_bwd(res, cts):
        stacked_host, h_stash, extras = res
        dh_out, daux = cts
        gacc = jax.tree.map(
            lambda p: _put(jnp.zeros(p.shape, jnp.float32), HOST),
            stacked_host)

        def body(carry, i):
            dh, gacc = carry
            lp = fetch_slice(stacked_host, i)
            h_in = jax.tree.map(lambda s: s[i], h_stash)

            def apply(lp_, h_):
                return step_fn(lp_, h_, extras, i)

            _, pull = jax.vjp(apply, lp, h_in)
            dlp, dh_in = pull((dh, daux.astype(jnp.float32)))
            gacc = park_slice(gacc, dlp, i)
            return (dh_in, gacc), None

        (dh0, gacc), _ = lax.scan(body, (dh_out, gacc),
                                  jnp.arange(steps - 1, -1, -1))
        # accumulation runs in fp32; the cotangent handed back to JAX must
        # match the primal dtype (custom_vjp checks avals), so cast at the
        # boundary for non-fp32 parameter trees
        gacc = jax.tree.map(
            lambda g, p: g if g.dtype == p.dtype else _put(
                g.astype(p.dtype), HOST),
            gacc, stacked_host)
        return gacc, dh0, None

    run.defvjp(run_fwd, run_bwd)
    h, aux = run(stacked_host, h0, extras)
    return h, aux


def streamed_tree_add(a_host, b_host):
    """``a + b`` over stacked host trees, one layer slice at a time."""
    steps = jax.tree.leaves(a_host)[0].shape[0]

    def body(acc, i):
        s = jax.tree.map(jnp.add, fetch_slice(a_host, i),
                         fetch_slice(b_host, i))
        return park_slice(acc, s, i), None

    zero = jax.tree.map(
        lambda p: _put(jnp.zeros(p.shape, p.dtype), HOST), a_host)
    acc, _ = lax.scan(body, zero, jnp.arange(steps))
    return acc


def streamed_sq_norm(tree_host):
    """Global squared L2 norm of a stacked host tree, slice-wise."""
    steps = jax.tree.leaves(tree_host)[0].shape[0]

    def body(acc, i):
        sl = fetch_slice(tree_host, i)
        s = sum(jnp.sum(x.astype(jnp.float32) ** 2)
                for x in jax.tree.leaves(sl))
        return acc + s, None

    acc, _ = lax.scan(body, jnp.zeros((), jnp.float32), jnp.arange(steps))
    return acc


def streamed_update(update_fn: Callable, grads_host, state_host, params_host,
                    lr, scale=None, gate=None):
    """Optimizer step over host-resident stacked trees, one layer at a time
    (ref PartitionedOptimizerSwapper, swap_tensor/partitioned_optimizer_
    swapper.py:27 — swap in a partition, step it, swap out).

    ``update_fn(grads, state, params, lr) -> (params, state)`` is applied
    to per-layer slices.  State leaves whose leading dim matches the layer
    count are sliced; scalars (e.g. adam's ``count``) pass through and are
    taken from the **last** slice call so they advance exactly once.
    ``scale`` optionally multiplies gradients slice-wise (loss-scale /
    grad-accum normalization + clipping coefficient, fused into the same
    pass so no full-size intermediate ever materialises).
    """
    steps = jax.tree.leaves(params_host)[0].shape[0]

    def is_stacked(x):
        return hasattr(x, "shape") and x.ndim >= 1 and x.shape[0] == steps

    def state_slice(state, i):
        return jax.tree.map(
            lambda x: _put(lax.dynamic_index_in_dim(x, i, keepdims=False),
                           DEVICE)
            if is_stacked(x) else x, state)

    def body(carry, i):
        p_acc, s_acc = carry
        g = fetch_slice(grads_host, i)
        if scale is not None:
            g = jax.tree.map(lambda x: x * scale, g)
        p = fetch_slice(params_host, i)
        s = state_slice(state_host, i)
        new_p, new_s = update_fn(g, s, p, lr)
        if gate is not None:
            # loss-scale overflow skip: keep the old slice, branch-free
            new_p = jax.tree.map(lambda n, o: jnp.where(gate, n, o), new_p, p)
            new_s = jax.tree.map(lambda n, o: jnp.where(gate, n, o.astype(n.dtype)),
                                 new_s, s)
        p_acc = park_slice(p_acc, new_p, i)
        s_acc = jax.tree.map(
            lambda a, n: lax.dynamic_update_index_in_dim(
                _put(a, HOST), _put(n.astype(a.dtype), HOST), i, axis=0)
            if is_stacked(a) else n,
            s_acc, new_s)
        return (p_acc, s_acc), None

    p0 = jax.tree.map(
        lambda p: _put(jnp.zeros(p.shape, p.dtype), HOST), params_host)
    # carry types must be stable: stacked state leaves live in host space
    # throughout the scan, and non-stacked ones (adam's scalar count) on
    # device — update_fn returns device scalars, so a host-typed input
    # would flip memory space across the carry
    state_host = jax.tree.map(
        lambda x: _put(x, HOST) if is_stacked(x) else _put(x, DEVICE),
        state_host)
    (new_params, new_state), _ = lax.scan(
        body, (p0, state_host), jnp.arange(steps))
    return new_params, new_state
