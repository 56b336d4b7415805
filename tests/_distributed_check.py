"""Subprocess body: run the distributed miner on an 8-device host mesh and
compare against the single-device batch/NOAC engines — prime and NOAC
variants, both merge strategies, bit-identical signatures and the same
kept tuples; then NOAC shuffles on 4 devices whose owners take the
shared δ-window bounds (rank-threshold runs over a two-word key, and a
64-bit key's in-segment search), checked against the paper oracle too.
Invoked by test_core_distributed.py; prints 'OK' on success."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax

from repro.core import (BatchMiner, DistributedMiner, NOACMiner,
                        PolyadicContext, pad_tuples, pad_values)
from repro.core import keys as K
from repro.core import reference as R
from repro.data import synthetic
from repro.launch.mesh import make_mesh
from repro.obs import Obs


def _compare(got, want):
    assert int(got.overflow) == 0, f"overflow={int(got.overflow)}"
    for name in ["sig_lo", "sig_hi", "gen_count", "volume", "density"]:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=name)
    # every engine keeps a cluster at its lowest-index first occurrence
    # of a distinct generating row, so the flags agree tuple for tuple
    for name in ["is_unique", "keep"]:
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)
    assert int(got.n_clusters) == int(np.asarray(want.is_unique).sum())


def check(mesh, axes, strategy, sizes, t, theta, seed):
    ctx = synthetic.random_context(sizes, t, seed=seed)
    n_sh = int(np.prod([mesh.shape[a] for a in
                        ((axes,) if isinstance(axes, str) else axes)]))
    tuples = pad_tuples(ctx.tuples, n_sh)
    bm = BatchMiner(sizes, theta=theta)
    want = bm(tuples)
    dm = DistributedMiner(sizes, mesh, axes=axes, theta=theta,
                          strategy=strategy)
    _compare(dm(tuples), want)


def check_noac(mesh, axes, strategy, sizes, t, delta, rho_min, minsup, seed):
    ctx = synthetic.random_context(sizes, t, seed=seed,
                                   values=True).deduplicated()
    n_sh = int(np.prod([mesh.shape[a] for a in
                        ((axes,) if isinstance(axes, str) else axes)]))
    tuples = pad_tuples(ctx.tuples, n_sh)
    values = pad_values(ctx.values, n_sh)
    nm = NOACMiner(sizes, delta=delta, rho_min=rho_min, minsup=minsup)
    want = nm(tuples, values)
    dm = DistributedMiner(sizes, mesh, axes=axes, strategy=strategy,
                          delta=delta, rho_min=rho_min, minsup=minsup)
    _compare(dm(tuples, values), want)


HALF_STARS = np.arange(1, 11, dtype=np.float32) / 2     # 0.5 .. 5.0


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


def bounds_paths(obs) -> dict:
    doc = obs.metrics.to_dict().get("pipeline_delta_bounds_total",
                                    {"series": []})
    return {r["labels"]["path"]: r["value"] for r in doc["series"]}


def check_owner_bounds(mesh, strategy, sizes, values, delta, seed, path,
                       **kw):
    """Bit-identity with ``NOACMiner``, the kept clusters of the paper
    oracle, and the δ-window path every owner (or the replicated
    pipeline) counts: one per mode."""
    ctx = rated_context(sizes, 3000, seed, values)
    tuples = pad_tuples(ctx.tuples, 4)
    vals = pad_values(ctx.values, 4)
    nm = NOACMiner(sizes, delta=delta, **kw)
    want = nm(tuples, vals)
    obs = Obs.create()
    dm = DistributedMiner(sizes, mesh, axes="data", strategy=strategy,
                          delta=delta, obs=obs, **kw)
    got = dm(tuples, vals)
    _compare(got, want)
    for name in ["sig_lo", "sig_hi", "gen_count", "volume", "density",
                 "cardinalities"]:
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)
    assert bounds_paths(obs) == {path: len(sizes)}, bounds_paths(obs)
    mined = sorted(tuple(sorted(map(sorted, c)))
                   for c, _ in nm.materialise(want))
    oracle = sorted(tuple(sorted(map(sorted, c)))
                    for c in R.noac(ctx, delta))
    assert mined == oracle
    assert int(np.asarray(got.keep).sum()) == len(oracle)


def main():
    mesh8 = make_mesh((8,), ("data",))
    mesh2x4 = make_mesh((2, 4), ("pod", "data"))
    for strategy in ("replicate", "shuffle"):
        check(mesh8, "data", strategy, (9, 7, 5), 160, 0.0, seed=0)
        check(mesh8, "data", strategy, (6, 6, 6, 4), 240, 0.3, seed=1)
        check(mesh2x4, ("pod", "data"), strategy, (9, 7, 5), 160, 0.0, seed=2)
        check_noac(mesh8, "data", strategy, (9, 7, 5), 160, 120.0, 0.0, 0,
                   seed=3)
        check_noac(mesh2x4, ("pod", "data"), strategy, (7, 6, 5), 120, 80.0,
                   0.3, 2, seed=4)
    mesh4 = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("data",),
                              axis_types=(jax.sharding.AxisType.Auto,))
    # 18 + 16 + 4 entity bits and a 4-bit rank lane over D = 10 values:
    # a 42-bit two-word key (43 with the owner's validity bit)
    stars = (2**18 - 1, 2**16 - 1, 10)
    plan = K.plan_context_keys(stars, True, len(HALF_STARS))[0]
    assert (plan.total_bits, plan.words, plan.value_bits) == (42, 2, 4)
    for delta in (1.0, 0.0):
        check_owner_bounds(mesh4, "shuffle", stars, HALF_STARS, delta,
                           seed=5, path="runs")
    check_owner_bounds(mesh4, "replicate", stars, HALF_STARS, 1.0, seed=6,
                       path="runs")
    # a float lane: owners search the validity-extended words globally
    # (63 live bits), or, at exactly 64, inside each segment
    floats = np.linspace(-3.0, 3.0, 25, dtype=np.float32)
    for sizes, bits, seed in (((2**11, 2**11, 2**9), 63, 7),
                              ((2**11, 2**11, 2**10), 64, 8)):
        assert K.plan_context_keys(sizes, True)[0].total_bits == bits
        check_owner_bounds(mesh4, "shuffle", sizes, floats, 0.5, seed=seed,
                           path="search", prune_values=False)
    print("OK")


if __name__ == "__main__":
    main()
