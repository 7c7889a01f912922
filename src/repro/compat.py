"""The jax mesh and ``shard_map`` surface every call site in this repo uses.

``make_mesh`` pins every axis to ``AxisType.Auto``. ``shard_map`` takes
``axis_names`` as any iterable (``jax.shard_map`` wants a set) and passes
``check_vma`` through only when the caller sets it, so partial-manual call
sites name their manual axes the same way everywhere.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "shard_map"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
