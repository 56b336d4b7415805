"""The two jax calls every mesh user shares, with the arguments this
repository always passes (single home; see DESIGN.md §6)."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication (vma) checking: the mining
    bodies return per-shard blocks the checker cannot type."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, names):
    """``jax.make_mesh`` with every axis of type Auto (GSPMD-propagated
    shardings, as ``shard_map`` and the model layers expect)."""
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))
