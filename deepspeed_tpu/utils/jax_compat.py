"""The one file that spells the jax APIs this repo leans on.

Written for the installed jax (0.9): ``jax.shard_map`` with
``axis_names``/``check_vma``, ``jax.lax.axis_size``, ``jax.memory.Space``,
``jax.sharding.get_abstract_mesh``, ``pltpu.CompilerParams``.  Every call
site imports these helpers and the seam lint (``analysis/seam.py``) keeps
the spellings out of the rest of the tree, so a jax release that moves
one of them is repaired here and nowhere else.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "get_abstract_mesh", "tpu_compiler_params",
           "axis_size", "manual_axis_names", "all_axes_manual",
           "with_unit_axes", "memory_spaces"]


def memory_spaces():
    """``(HOST, DEVICE)`` placement targets for ``device_put`` inside
    jit."""
    return jax.memory.Space.Host, jax.memory.Space.Device


def axis_size(axis_name) -> int:
    """Static size of a bound mesh axis (or product over a sequence of
    axes) inside shard_map."""
    names = ((axis_name,) if isinstance(axis_name, str) else tuple(axis_name))
    n = 1
    for name in names:
        n *= int(jax.lax.axis_size(name))
    return n


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map``.  ``axis_names``: the axes the body is *manual*
    over (None = all mesh axes)."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def get_abstract_mesh():
    """Current abstract mesh context (``.empty`` when not under one)."""
    return jax.sharding.get_abstract_mesh()


def manual_axis_names():
    """Mesh axes currently bound MANUALLY (i.e. we are inside a shard_map
    body over them).  A ``with_sharding_constraint`` naming one of them
    is an error, and inside a manual region per-shard layouts are
    explicit, so layout-hint call sites consult this set and skip the
    hint."""
    return set(get_abstract_mesh().manual_axes)


def all_axes_manual() -> bool:
    """Whether every axis of the current mesh context is manual (or there
    is no mesh context).  Mosaic kernels cannot be partitioned
    automatically, so this is the only place the chip's compiler takes
    one in a program that spans several devices."""
    ctx = get_abstract_mesh()
    return ctx.empty or set(ctx.manual_axes) == set(ctx.axis_names)


def with_unit_axes(mesh, axis_names) -> set:
    """``axis_names`` plus every size-1 axis of ``mesh`` that is not
    manual yet, for a ``shard_map`` that holds a Mosaic kernel: going
    manual over a size-1 axis changes nothing, and it is what makes
    :func:`all_axes_manual` true inside when no other axis is sharded."""
    outer = set(getattr(mesh, "manual_axes", ()))
    return set(axis_names) | {a for a in mesh.axis_names
                              if mesh.shape[a] == 1 and a not in outer}


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams``."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
