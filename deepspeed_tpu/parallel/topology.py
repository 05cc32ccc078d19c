"""Logical device mesh topology.

TPU-native replacement for the reference's process-group machinery
(``deepspeed/utils/groups.py`` + ``runtime/pipe/topology.py``): instead of
materialising torch ProcessGroups per parallelism dimension, we build ONE
``jax.sharding.Mesh`` whose named axes play the role of the reference's
DP/TP/PP/EP/SP groups.  Collectives are expressed against axis names and XLA
lowers them onto ICI/DCN.

Axis order is outer→inner ``(pipe, data, expert, seq, tensor)`` so that the
innermost axes (tensor, seq) — which carry the highest-bandwidth collectives
— map onto adjacent devices/ICI, while pipe/data may ride DCN across hosts.
This mirrors ``PipeModelDataParallelTopology`` (ref topology.py) where model
parallel is innermost.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.logging import logger

# Canonical axis names, outer→inner.
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
# Inner factor of the DP world for hierarchical partitioning: ZeRO++ hpZ
# secondary partition / MiCS sub-groups (ref zero_hpz_partition_size,
# runtime/zero/config.py:300; MiCS_Init, runtime/zero/mics.py:63).  Size 1
# unless the engine factors the DP world; "data" is then the *outer*
# (replication / DCN) factor and "subdata" the *inner* (shard / ICI) one.
SUBDATA_AXIS = "subdata"
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
TENSOR_AXIS = "tensor"
MESH_AXES: Tuple[str, ...] = (PIPE_AXIS, DATA_AXIS, SUBDATA_AXIS, EXPERT_AXIS,
                              SEQ_AXIS, TENSOR_AXIS)

# Axes over which the *global batch* is sharded (ref: DP world = data×expert;
# groups._create_expert_and_data_parallel, groups.py:240).
BATCH_AXES: Tuple[str, ...] = (DATA_AXIS, SUBDATA_AXIS, EXPERT_AXIS)
# Axes over which ZeRO partitions optimizer/gradient/parameter state.
ZERO_AXES: Tuple[str, ...] = (DATA_AXIS, SUBDATA_AXIS, EXPERT_AXIS, SEQ_AXIS)
# Inner (ICI-adjacent) ZeRO axes: the secondary partition group for hpZ
# params / the MiCS shard group.
ZERO_INNER_AXES: Tuple[str, ...] = (SUBDATA_AXIS, EXPERT_AXIS, SEQ_AXIS)


def resolve_mesh_sizes(sizes: Optional[Dict[str, int]], n_devices: int) -> Dict[str, int]:
    """Resolve axis sizes: missing axes default to 1 ("data" defaults to -1),
    one axis may be -1 (inferred). Product < n_devices → submesh (warn).
    Single source of truth shared by MeshTopology and the config system."""
    sizes = dict(sizes or {})
    if DATA_AXIS not in sizes:
        sizes[DATA_AXIS] = -1  # absorb remaining devices by default
    for ax in MESH_AXES:
        sizes.setdefault(ax, 1)
    for ax, v in sizes.items():
        if v != -1 and v <= 0:
            raise ValueError(f"mesh axis {ax} must be positive or -1, got {v}")
    unknown = [ax for ax in MESH_AXES if sizes[ax] == -1]
    prod = int(np.prod([sizes[ax] for ax in MESH_AXES if sizes[ax] != -1]))
    if len(unknown) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if unknown:
        if n_devices % prod != 0:
            raise ValueError(f"{n_devices} devices not divisible by {prod}")
        sizes[unknown[0]] = n_devices // prod
    elif prod > n_devices:
        raise ValueError(f"mesh sizes {sizes} product {prod} > {n_devices} devices")
    elif prod < n_devices:
        logger.warning(f"mesh product {prod} < {n_devices} devices; using a submesh")
    return {ax: int(sizes[ax]) for ax in MESH_AXES}


def factor_data_axis(sizes: Dict[str, int], shard_size: int) -> Dict[str, int]:
    """Factor the resolved data axis into (outer=data, inner=subdata) for
    hierarchical partitioning (hpZ secondary partition / MiCS sub-groups).

    ``shard_size`` devices form the inner shard group (ICI-adjacent); the
    remaining data-parallel factor replicates across them.
    """
    sizes = dict(sizes)
    data = sizes.get(DATA_AXIS, 1) * sizes.get(SUBDATA_AXIS, 1)
    if shard_size <= 0 or data % shard_size != 0:
        raise ValueError(f"data-parallel world {data} not divisible by "
                         f"secondary partition size {shard_size}")
    sizes[DATA_AXIS] = data // shard_size
    sizes[SUBDATA_AXIS] = shard_size
    return sizes


class MeshTopology:
    """A resolved logical mesh over the available devices.

    ``sizes`` maps axis name → size; missing axes default to 1; one axis may
    be -1 (inferred).  The mesh is the single source of truth for every
    "process group" query the reference exposes (``get_data_parallel_world_size``
    etc., ref groups.py:110-663).
    """

    def __init__(self, sizes: Optional[Dict[str, int]] = None,
                 devices: Optional[Sequence] = None):
        devices = list(devices if devices is not None else jax.devices())
        sizes = resolve_mesh_sizes(sizes, len(devices))
        prod = int(np.prod(list(sizes.values())))
        devices = devices[:prod]
        n = prod

        self.sizes: Dict[str, int] = {ax: int(sizes[ax]) for ax in MESH_AXES}
        shape = tuple(self.sizes[ax] for ax in MESH_AXES)
        if n > 1:
            from jax.experimental import mesh_utils

            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        else:
            dev_array = np.asarray(devices).reshape(shape)
        self.mesh = Mesh(dev_array, MESH_AXES)
        logger.info(f"MeshTopology: {self.sizes} over {n} device(s)")

    # -- world-size getters (ref groups.py getters) --------------------
    @property
    def world_size(self) -> int:
        return int(np.prod(list(self.sizes.values())))

    def axis_size(self, axis: str) -> int:
        return self.sizes[axis]

    @property
    def dp_size(self) -> int:
        """Data-parallel world as the reference defines it (data×expert)."""
        return (self.sizes[DATA_AXIS] * self.sizes[SUBDATA_AXIS]
                * self.sizes[EXPERT_AXIS])

    @property
    def zero_size(self) -> int:
        """World over which ZeRO shards state (data×expert×seq): sequence
        parallel ranks hold identical params so they join the ZeRO shard
        group, matching Ulysses+ZeRO-3 composition (ref ulysses_sp.py)."""
        return self.dp_size * self.sizes[SEQ_AXIS]

    @property
    def tp_size(self) -> int:
        return self.sizes[TENSOR_AXIS]

    @property
    def pp_size(self) -> int:
        return self.sizes[PIPE_AXIS]

    @property
    def ep_size(self) -> int:
        return self.sizes[EXPERT_AXIS]

    @property
    def sp_size(self) -> int:
        return self.sizes[SEQ_AXIS]

    # -- sharding helpers ----------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self, seq_dim: Optional[int] = None,
                       batch_dim: int = 0, ndim: int = 2) -> NamedSharding:
        """Sharding for a batch array: batch dim over (data, expert), and the
        sequence dim over seq when sequence parallelism is active."""
        spec: List = [None] * ndim
        spec[batch_dim] = BATCH_AXES
        if seq_dim is not None and self.sp_size > 1:
            spec[seq_dim] = SEQ_AXIS
        return NamedSharding(self.mesh, P(*spec))

    def __repr__(self) -> str:  # pragma: no cover
        return f"MeshTopology({self.sizes})"


_GLOBAL_TOPOLOGY: Optional[MeshTopology] = None


def set_topology(topo: MeshTopology) -> None:
    global _GLOBAL_TOPOLOGY
    _GLOBAL_TOPOLOGY = topo


def get_topology() -> Optional[MeshTopology]:
    return _GLOBAL_TOPOLOGY
