"""The two jax calls every call site makes with the same fixed arguments.

Written against the installed jax (see ``pyproject.toml``); there are no
version branches.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (``check_vma``): the
    SpGEMM executors return per-device shards on purpose."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (jax's default is Explicit)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
