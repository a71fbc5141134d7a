"""The one home of the jax mesh and shard_map surface the repo uses.

Targets the installed jax (0.9): ``jax.make_mesh(axis_types=...)`` with
``jax.sharding.AxisType`` and ``jax.shard_map(check_vma=...,
axis_names=...)``. Everything in the repo builds meshes and shard_maps
through these two helpers, so the defaults below hold everywhere.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names), **kw)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False,
              axis_names=None):
    """``jax.shard_map`` with ``check_vma`` off by default. ``axis_names``
    (manual axes; the rest stay auto) narrows the manual region."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)
