"""The engine-conformance contexts: the benchmark's data shapes at
<= 3,000 rows, each from a seeded generator, with the variant and the
parameters it is mined with.  Shared by the in-process route matrix
(``test_conformance_*.py``) and the multi-device subprocess
(``_distributed_check.py``)."""
from __future__ import annotations

import functools

import numpy as np
import pytest

from repro.core import PolyadicContext, mine
from repro.core import keys as K
from repro.core import radix as RX
from repro.core import reference as R
from repro.core.postprocess import cluster_set
from repro.data import synthetic

HALF_STARS = np.arange(1, 11, dtype=np.float32) / 2     # 0.5 .. 5.0
FLOATS = np.linspace(-3.0, 3.0, 25, dtype=np.float32)   # 0.25 apart


def rated_context(sizes, t, seed, values):
    """``t`` draws of (user, movie, tag) from 40 x 30 ids spread over
    the modes' whole id ranges and ``sizes[2]`` tags, each with a value
    drawn apart from its ids, so key segments hold several rows and
    δ-windows split them (the constructor keeps one row per tuple)."""
    rng = np.random.default_rng(seed)
    users = rng.choice(sizes[0], 40, replace=False)
    movies = rng.choice(sizes[1], 30, replace=False)
    rows = np.stack([users[rng.integers(0, 40, t)],
                     movies[rng.integers(0, 30, t)],
                     rng.integers(0, sizes[2], t)], 1)
    return PolyadicContext(sizes, rows, values[rng.integers(0, len(values),
                                                            t)])


def _one_star_per_pair(seed):
    """MovieLens-shaped ratings: the first star each (user, movie) pair
    drew, the star both the third mode and the value (D = 5)."""
    ctx = synthetic.movielens_like(n_tuples=3000, seed=seed)
    _, first = np.unique(ctx.tuples[:, :2], axis=0, return_index=True)
    first.sort()
    return PolyadicContext(ctx.sizes, ctx.tuples[first], ctx.values[first])


#: name -> (context builder, variant, mine() parameters of the variant)
CONTEXTS = {
    # power-law folksonomy at BibSonomy's mode sizes, triples drawn with
    # replacement: a 44-bit key
    "folksonomy_prime": (
        lambda: synthetic.bibsonomy_like(n_tuples=3000, seed=11),
        "prime", {"theta": 0.0}),
    # one star per (user, movie) at ml-1m's mode sizes, D = 5
    "ratings_d5_noac": (
        lambda: _one_star_per_pair(seed=12), "noac", {"delta": 1.0}),
    # half stars over 2^18 x 2^16 ids: a 42-bit two-word key with a
    # 4-bit rank lane (70 bits with the float lane)
    "half_stars_d10_noac": (
        lambda: rated_context((2**18 - 1, 2**16 - 1, 10), 3000, 13,
                              HALF_STARS),
        "noac", {"delta": 1.0}),
    # the paper's dense arity-4 worst case: one cluster of 2,401 rows
    "dense_4d_prime": (
        lambda: synthetic.k3_dense_4d(n=7), "prime", {"theta": 0.0}),
    # a float value lane (no rank coding): δ-windows by binary search
    "float_lane_noac": (
        lambda: rated_context((2**9, 2**9, 8), 3000, 14, FLOATS),
        "noac", {"delta": 0.5, "prune_values": False}),
    # power-law subject x verb x object frames; their frequencies make
    # the context keep one row per triple
    "frames_prime": (
        lambda: synthetic.semantic_frames_like(n_tuples=3000, seed=15),
        "prime", {"theta": 0.0}),
}


@functools.lru_cache(None)
def context(name: str) -> PolyadicContext:
    return CONTEXTS[name][0]()


@functools.lru_cache(None)
def oracle(name: str) -> frozenset:
    """The kept clusters of ``core/reference.py``, the paper's oracle."""
    ctx = context(name)
    _, variant, params = CONTEXTS[name]
    if variant == "prime":
        _, _, _, kept = R.multimodal_clusters(ctx, theta=params["theta"])
    else:
        kept = R.noac(ctx.deduplicated(), params["delta"])
    return frozenset(cluster_set((cl, None) for cl in kept))


# ---------------------------------------------------------------------------
# The routes of ``core.engines.mine`` and the check of one route
# ---------------------------------------------------------------------------

ROUTES = ("batch", "windowed", "chunked", "streaming", "replicate",
          "shuffle")


def route_params(route: str, t: int) -> dict:
    """``mine()`` arguments of ``route`` on a ``t``-row table: at least
    four device windows, at least three host chunks, and the one-device
    mesh for the distributed strategies."""
    if route == "windowed":
        budget = -(-t // 4)
        assert RX.plan_windows(t, budget).n_windows >= 4
        return {"backend": "batch", "window_budget": budget}
    if route == "chunked":
        budget = -(-t // 3)
        assert -(-t // budget) >= 3
        return {"backend": "batch", "chunk_budget": budget}
    if route in ("replicate", "shuffle"):
        return {"backend": "distributed", "strategy": route}
    return {"backend": route}


def _mine(name: str, route: str):
    ctx = context(name)
    _, variant, params = CONTEXTS[name]
    return mine(ctx, variant=variant, **params,
                **route_params(route, ctx.num_tuples))


@functools.lru_cache(None)
def _batch(name: str):
    return _mine(name, "batch")


def windowed_rejects(name: str) -> bool:
    """``mine_windowed`` refuses a key wider than 64 bits with its
    engine's (float-lane) plan."""
    ctx = context(name)
    return not K.plan_context_keys(ctx.sizes,
                                   CONTEXTS[name][1] == "noac")[0].fits


def check_route(name: str, route: str) -> None:
    """``route`` keeps exactly the oracle's clusters of context ``name``,
    and its signatures, ``keep`` and density equal the batch route's
    tuple for tuple; a combination the engine refuses by design raises
    its documented error."""
    if route == "windowed" and windowed_rejects(name):
        with pytest.raises(ValueError, match="64-bit-packable"):
            _mine(name, route)
        return
    run = _mine(name, route)
    want = _batch(name).result
    t = want.keep.shape[0]
    # a streaming snapshot pads the table with copies of row 0
    assert not np.asarray(run.result.keep)[t:].any()
    for f in ("sig_lo", "sig_hi", "keep", "density"):
        np.testing.assert_array_equal(np.asarray(getattr(run.result, f))[:t],
                                      np.asarray(getattr(want, f)),
                                      err_msg=f)
    assert run.n_clusters == len(oracle(name))
    if run.clusters is not None:
        assert cluster_set(run.clusters) == oracle(name)
