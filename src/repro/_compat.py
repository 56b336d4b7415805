"""The jax calls shared across the repository, with the arguments it
always passes (single home): the two every mesh user makes (DESIGN.md
§7), and the cumulative scans of the mining pipeline."""
from __future__ import annotations

import jax
import numpy as np


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication (vma) checking: the mining
    bodies return per-shard blocks the checker cannot type."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, names):
    """``jax.make_mesh`` with every axis of type Auto (GSPMD-propagated
    shardings, as ``shard_map`` expects)."""
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def cumulative(x, op, *, axis: int = 0, reverse: bool = False):
    """Inclusive scan of ``x`` along ``axis`` by ``op`` (``jax.lax.add``,
    ``max`` or ``min``): the very reduce-window that ``lax.cumsum`` /
    ``cummax`` / ``cummin`` lower to, written in place.  Those lower out
    of line, so their operations lose the caller's ``jax.named_scope``
    (DESIGN.md §11); this one keeps it."""
    n = x.shape[axis]
    if n == 0:
        return x
    dt = np.dtype(x.dtype)
    big = np.iinfo(dt).max if dt.kind in "iu" else np.inf
    low = np.iinfo(dt).min if dt.kind in "iu" else -np.inf
    init = {jax.lax.add: 0, jax.lax.max: low, jax.lax.min: big}[op]
    dims, pad = [1] * x.ndim, [(0, 0)] * x.ndim
    dims[axis] = n
    pad[axis] = (0, n - 1) if reverse else (n - 1, 0)
    return jax.lax.reduce_window(x, np.array(init, dt), op, tuple(dims),
                                 (1,) * x.ndim, pad)
