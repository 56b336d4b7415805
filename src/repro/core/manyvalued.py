"""Many-valued δ-triclustering (paper §3.2) / NOAC (paper §4.3).

A thin driver over the shared Stage-1/2/3 pipeline (``core.pipeline``,
DESIGN.md §3) with the *δ-range* component operator: each mode's table is
sorted by (other columns, value), so every δ-cumulus is a contiguous
value range inside a contiguous key segment, found with rank-threshold
scans or two vectorised binary searches (``pipeline._delta_bounds``) —
O(T log T) at most, versus the O(T · |A_k|) dictionary
walks of the C#/.Net NOAC implementation the paper benchmarks in §6.

Set signatures of ranges come from per-mode prefix sums of
first-occurrence-masked uint32 hash weights (modular arithmetic makes
range differences exact), so the engine is duplicate-idempotent like the
prime variant: V must be a *function* of the tuple (paper §3.2), but the
tuple table itself may contain duplicates (e.g. shard padding or
at-least-once delivery) without changing any output.

Validity checks (per §4.3): minimal per-mode cardinality (minsup) and
minimal density ρ_min, with density estimated exactly as the M/R stage 3
does (distinct generating tuples / volume), so all engines agree.  NOAC
also runs distributed (core/distributed.py, both merge strategies) and
streaming (core/streaming.py) through the same pipeline, bit-identical
to this single-shard engine.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from . import pipeline as P
from .context import PolyadicContext

_bsearch = P.bsearch                 # canonical home: core.pipeline
NOACResult = P.PipelineResult        # unified result type


def noac_mine(tuples, values, hash_lo, hash_hi, delta: float,
              rho_min: float = 0.0, minsup: int = 0) -> NOACResult:
    """The full three-stage δ pipeline on one shard (jit-able)."""
    return P.mine_tuples(tuples, hash_lo, hash_hi, values=values,
                         delta=delta, theta=rho_min, minsup=minsup)


class NOACMiner(P.PipelineMiner):
    """jit-compiled many-valued (δ) multimodal clustering."""

    def __init__(self, sizes: Sequence[int], delta: float,
                 rho_min: float = 0.0, minsup: int = 0, seed: int = 0x5EED,
                 packed: Optional[bool] = None,
                 sort_backend: Optional[str] = None,
                 use_pallas: Optional[bool] = None,
                 prune_values: bool = True,
                 window_budget: Optional[int] = None):
        super().__init__(sizes, theta=rho_min, delta=delta, minsup=minsup,
                         seed=seed, packed=packed,
                         sort_backend=sort_backend, use_pallas=use_pallas,
                         prune_values=prune_values,
                         window_budget=window_budget)
        self.rho_min = float(rho_min)

    def mine_context(self, ctx: PolyadicContext):
        if ctx.values is None:
            # §3.2: W={0,1}, δ=0 degenerates to prime operators
            ctx = PolyadicContext(ctx.sizes, ctx.tuples,
                                  np.zeros(ctx.num_tuples, np.float32),
                                  ctx.names)
        ctx = ctx.deduplicated()
        return self.materialise(self(ctx.tuples, ctx.values), ctx.tuples)
