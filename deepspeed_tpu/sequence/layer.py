"""Ulysses sequence parallelism.

Re-design of ``deepspeed/sequence/layer.py`` (DistributedAttention :331,
``_SeqAllToAll`` :277, ``single_all_to_all`` :221): activations are
sequence-sharded everywhere except inside attention, which is head-sharded;
the layout switch seq-sharded ↔ head-sharded is an all-to-all over the
"seq" mesh axis.

Two equivalent TPU-native realisations are provided:

* :func:`ulysses_sharding_constraints` — the GSPMD form used by the engine's
  compiled path: ``with_sharding_constraint`` pins q/k/v to head-sharded and
  the attention output back to seq-sharded, and XLA lowers the resharding to
  ICI all-to-alls (verified in tests by inspecting the HLO).  This is the
  idiomatic replacement for the reference's explicit ``dist.all_to_all``.
* :class:`DistributedAttention` — an explicit ``shard_map`` wrapper with
  hand-written ``lax.all_to_all`` for API parity with the reference (usable
  with any local attention callable).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import (BATCH_AXES, SEQ_AXIS,
                                             TENSOR_AXIS, MeshTopology,
                                             get_topology)
from deepspeed_tpu.utils.jax_compat import manual_axis_names, shard_map


def _constraint(x, spec):
    topo = get_topology()
    if topo is None:
        return x
    manual = manual_axis_names()
    if manual:
        # inside a shard_map body (e.g. the pipeline stage_fn): a
        # constraint naming a manually-bound axis is a hard partitioner
        # error, and inside a manual region per-shard layouts are
        # explicit so the hint buys nothing — skip it
        named = {a for part in spec if part is not None
                 for a in (part if isinstance(part, (tuple, list))
                           else (part,))}
        if named & manual:
            return x
    return lax.with_sharding_constraint(x, NamedSharding(topo.mesh, spec))


def ulysses_qkv_constraint(q, k, v):
    """Pin q/k/v [B, S, H, D] to head-sharded over the seq axis (XLA inserts
    the seq→head all-to-all). KV heads may be fewer than sp_size (GQA): then
    KV stays seq-sharded and XLA all-gathers inside attention instead.

    Composed with tensor parallelism the heads are already tp-sharded, so
    the target layout shards heads JOINTLY over (tensor, seq) — pinning
    them to seq alone asks the partitioner for a tensor→seq re-layout it
    cannot express and it hard-aborts. Requires heads % (tp·sp) == 0."""
    topo = get_topology()
    if topo is None or topo.sp_size == 1:
        return q, k, v
    sp, tp = topo.sp_size, topo.tp_size
    grp = sp * tp
    head_spec = (P(BATCH_AXES, None, (TENSOR_AXIS, SEQ_AXIS), None)
                 if tp > 1 else P(BATCH_AXES, None, SEQ_AXIS, None))
    q = _constraint(q, head_spec) if q.shape[2] % grp == 0 else q
    k = _constraint(k, head_spec) if k.shape[2] % grp == 0 else k
    v = _constraint(v, head_spec) if v.shape[2] % grp == 0 else v
    return q, k, v


def ulysses_output_constraint(out):
    """Pin attention output [B, S, H*D] back to seq-sharded (head→seq
    all-to-all).  Under tp the hidden dim stays TENSOR-sharded — that is
    the row-parallel wo matmul's natural input layout (its contracting dim
    is tp-sharded), so no tensor-axis all-gather is forced here."""
    topo = get_topology()
    if topo is None or topo.sp_size == 1:
        return out
    hid = TENSOR_AXIS if topo.tp_size > 1 else None
    return _constraint(out, P(BATCH_AXES, SEQ_AXIS, hid))


def single_all_to_all(x, scatter_idx: int, gather_idx: int, axis: str = SEQ_AXIS):
    """Explicit all-to-all layout switch (ref single_all_to_all, layer.py:221).
    Must run inside shard_map over ``axis``."""
    return lax.all_to_all(x, axis, split_axis=scatter_idx, concat_axis=gather_idx,
                          tiled=True)


class DistributedAttention:
    """Ulysses attention wrapper (ref DistributedAttention, layer.py:331).

    ``local_attn(q, k, v) -> out`` operates on [B, S_full, H_local, D].
    ``__call__`` takes seq-sharded q/k/v [B, S_local, H, D] *global* arrays
    and runs the scatter-heads/gather-seq a2a → attn → inverse pipeline
    under shard_map.
    """

    def __init__(self, local_attn: Callable, topology: Optional[MeshTopology] = None,
                 scatter_idx: int = 2, gather_idx: int = 1):
        self.local_attn = local_attn
        self.topo = topology or get_topology()
        self.scatter_idx = scatter_idx  # heads dim
        self.gather_idx = gather_idx  # seq dim

    def __call__(self, q, k, v):
        topo = self.topo or get_topology()
        if topo is None or topo.sp_size == 1:
            return self.local_attn(q, k, v)
        sp = topo.sp_size
        if q.shape[self.scatter_idx] % sp != 0:
            raise ValueError(
                f"query heads ({q.shape[self.scatter_idx]}) must be divisible by "
                f"sequence-parallel size {sp} (ref layer.py uneven-heads fallback)")
        if k.shape[self.scatter_idx] % sp != 0:
            # GQA with fewer KV heads than sp ranks: expand KV to the query
            # head count so the head scatter divides evenly (the reference's
            # uneven-head handling, sequence/layer.py:111).
            rep = q.shape[self.scatter_idx] // k.shape[self.scatter_idx]
            k = jnp.repeat(k, rep, axis=self.scatter_idx)
            v = jnp.repeat(v, rep, axis=self.scatter_idx)
        mesh = topo.mesh
        in_spec = P(BATCH_AXES, SEQ_AXIS, None, None)  # seq-sharded
        out_spec = in_spec

        def body(q_l, k_l, v_l):
            # [B, S/sp, H, D] → all-to-all → [B, S, H/sp, D]
            q_h = single_all_to_all(q_l, self.scatter_idx, self.gather_idx)
            k_h = single_all_to_all(k_l, self.scatter_idx, self.gather_idx)
            v_h = single_all_to_all(v_l, self.scatter_idx, self.gather_idx)
            out = self.local_attn(q_h, k_h, v_h)  # [B, S, H/sp, D]
            # inverse: scatter seq, gather heads
            return single_all_to_all(out, self.gather_idx, self.scatter_idx)

        return shard_map(body, mesh=mesh, in_specs=(in_spec, in_spec, in_spec),
                             out_specs=out_spec, check_vma=False)(q, k, v)


class UlyssesAttentionHF(DistributedAttention):
    """Alias mirroring the ALST HF integration entry point
    (ref runtime/sequence_parallel/ulysses_sp.py:49)."""
