"""Subprocess body: run the distributed miner on an 8-device host mesh and
compare against the single-device batch/NOAC engines — prime and NOAC
variants, both merge strategies, bit-identical signatures and the same
kept tuples; then NOAC shuffles on 4 devices whose owners take the
shared δ-window bounds (rank-threshold runs over a two-word key, and a
64-bit key's in-segment search), checked against the paper oracle too;
then the engine-conformance contexts (``_conformance.py``) under both
strategies on 4 devices.

Run by ``test_core_distributed.py`` (one case per entry of ``CHECKS``),
or by hand: ``PYTHONPATH=src python tests/_distributed_check.py``.
Prints one JSON verdict line per check, then 'OK' when all passed."""
import json
import os
import sys
import traceback

import numpy as np
import jax

import _conformance as C
from repro.core import (BatchMiner, DistributedMiner, NOACMiner,
                        pad_tuples, pad_values)
from repro.core import keys as K
from repro.core import reference as R
from repro.core.postprocess import cluster_set
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


def bounds_paths(obs) -> dict:
    doc = obs.metrics.to_dict().get("pipeline_delta_bounds_total",
                                    {"series": []})
    return {r["labels"]["path"]: r["value"] for r in doc["series"]}


def check_owner_bounds(mesh, strategy, sizes, values, delta, seed, path,
                       **kw):
    """Bit-identity with ``NOACMiner``, the kept clusters of the paper
    oracle, and the δ-window path every owner (or the replicated
    pipeline) counts: one per mode."""
    ctx = C.rated_context(sizes, 3000, seed, values)
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


def check_conformance(mesh, strategy, name):
    """A conformance context through ``strategy`` on ``mesh``:
    bit-identical to the one-device engine, which keeps exactly the
    paper oracle's clusters."""
    ctx = C.context(name)
    variant, params = C.CONTEXTS[name][1:]
    n_sh = mesh.shape["data"]
    tuples = pad_tuples(ctx.tuples, n_sh)
    if variant == "prime":
        miner, args = BatchMiner(ctx.sizes, **params), (tuples,)
    else:
        miner = NOACMiner(ctx.sizes, **params)
        args = (tuples, pad_values(ctx.values, n_sh))
    want = miner(*args)
    dm = DistributedMiner(ctx.sizes, mesh, axes="data", strategy=strategy,
                          **params)
    _compare(dm(*args), want)
    assert cluster_set(miner.materialise(want)) == C.oracle(name)


# 18 + 16 + 4 entity bits and a 4-bit rank lane over D = 10 values:
# a 42-bit two-word key (43 with the owner's validity bit)
STARS = (2**18 - 1, 2**16 - 1, 10)
# a float lane: owners search the validity-extended words globally
# (63 live bits), or, at exactly 64, inside each segment
FLOAT_SIZES = {63: (2**11, 2**11, 2**9), 64: (2**11, 2**11, 2**10)}

MESHES = {
    "mesh8": lambda: make_mesh((8,), ("data",)),
    "mesh2x4": lambda: make_mesh((2, 4), ("pod", "data")),
    "mesh4": lambda: jax.sharding.Mesh(
        np.asarray(jax.devices()[:4]), ("data",),
        axis_types=(jax.sharding.AxisType.Auto,)),
}
POD_DATA = ("pod", "data")

#: (name, check, mesh, arguments after the mesh) — one verdict each
CHECKS = [c for strategy in ("replicate", "shuffle") for c in [
    (f"prime-mesh8-{strategy}", check, "mesh8",
     ("data", strategy, (9, 7, 5), 160, 0.0), {"seed": 0}),
    (f"prime-4ary-theta-mesh8-{strategy}", check, "mesh8",
     ("data", strategy, (6, 6, 6, 4), 240, 0.3), {"seed": 1}),
    (f"prime-mesh2x4-{strategy}", check, "mesh2x4",
     (POD_DATA, strategy, (9, 7, 5), 160, 0.0), {"seed": 2}),
    (f"noac-mesh8-{strategy}", check_noac, "mesh8",
     ("data", strategy, (9, 7, 5), 160, 120.0, 0.0, 0), {"seed": 3}),
    (f"noac-rho-minsup-mesh2x4-{strategy}", check_noac, "mesh2x4",
     (POD_DATA, strategy, (7, 6, 5), 120, 80.0, 0.3, 2), {"seed": 4}),
]] + [
    ("owner-bounds-runs-delta1-shuffle", check_owner_bounds, "mesh4",
     ("shuffle", STARS, C.HALF_STARS, 1.0), {"seed": 5, "path": "runs"}),
    ("owner-bounds-runs-delta0-shuffle", check_owner_bounds, "mesh4",
     ("shuffle", STARS, C.HALF_STARS, 0.0), {"seed": 5, "path": "runs"}),
    ("owner-bounds-runs-delta1-replicate", check_owner_bounds, "mesh4",
     ("replicate", STARS, C.HALF_STARS, 1.0), {"seed": 6, "path": "runs"}),
] + [
    (f"owner-bounds-float-{bits}bit-shuffle", check_owner_bounds, "mesh4",
     ("shuffle", FLOAT_SIZES[bits], C.FLOATS, 0.5),
     {"seed": seed, "path": "search", "prune_values": False})
    for bits, seed in ((63, 7), (64, 8))
] + [
    (f"conformance-{name}-{strategy}", check_conformance, "mesh4",
     (strategy, name), {})
    for name in C.CONTEXTS for strategy in ("replicate", "shuffle")
]

CHECK_NAMES = [c[0] for c in CHECKS]


def main():
    stars = K.plan_context_keys(STARS, True, len(C.HALF_STARS))[0]
    assert (stars.total_bits, stars.words, stars.value_bits) == (42, 2, 4)
    for bits, sizes in FLOAT_SIZES.items():
        assert K.plan_context_keys(sizes, True)[0].total_bits == bits
    meshes = {}
    failed = 0
    for name, fn, mesh, args, kw in CHECKS:
        try:
            if mesh not in meshes:
                meshes[mesh] = MESHES[mesh]()
            fn(meshes[mesh], *args, **kw)
            verdict = {"check": name, "ok": True}
        except Exception:                         # one verdict per check
            failed += 1
            verdict = {"check": name, "ok": False,
                       "error": traceback.format_exc()}
        print(json.dumps(verdict), flush=True)
    if failed:
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    # before the first device query: the mesh needs 8 host devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.exit(main())
