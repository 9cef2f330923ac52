"""The repo's two mesh primitives, spelled once.

Everything that builds a mesh or wraps a function in shard_map goes
through here, so every mesh has all axes ``Auto`` and every shard_map
states its varying-manual-axes check explicitly.  Like ``launch.mesh``,
importing this module never touches jax device state.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """Map ``f`` over the mesh shards; ``check`` gates the
    varying-manual-axes check."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check,
    )
