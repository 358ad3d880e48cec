"""The repo's two mesh helpers: every mesh/shard_map construction in src,
tests, and benchmarks goes through them, so the axis types and the
shard_map checking mode are chosen in one place.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
