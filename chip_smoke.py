"""Smoke run of the mining and serving path on a TPU, through the normal
entry points, at the datasets' published scale.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: the distributed path only

Run it from the root of a checkout.  One process holds the chip and
runs every phase:

  a. device check: prints platform, device kind and count; exits
     non-zero unless JAX sees TPUs.
  b. batch mining of the BibSonomy-shaped context at its published
     scale (816,197 triples, 2,337 x 67,464 x 28,920; paper Table 2)
     with ``repro.core.mine(backend="batch")`` and the Pallas kernels
     compiled for the chip, again with ``window_budget`` set to a few
     windows, and with the jnp path (``use_pallas=False,
     sort_backend="lax"``).  All three results must be identical leaf
     for leaf, and a seeded prefix must match ``core/reference.py``.
  c. NOAC mining (delta = 1.0) of the MovieLens-shaped context, 1,000,000
     ratings over 6,040 x 3,952 x 5 stars, with the same checks.
  d. serving: ``TriclusterService(backend="streaming")`` preloaded with
     the BibSonomy context (at a quarter of its scale, see
     ``SERVE_SCALE``) in chunks, behind ``serve.protocol``'s HTTP
     server on localhost; entity, batch, top-k and signature queries,
     then an upsert and a delete, each read back with
     ``at_least_version``; no mine or publish error may be counted.

``--chips 4`` runs only ``DistributedMiner`` on a ``make_local_mesh()``
mesh (replicate and shuffle merges, prime and NOAC, MovieLens-shaped)
against ``BatchMiner`` on one device of the same process.

Timings printed along the way are one-shot smoke timings, not benchmark
numbers.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failing phase raises, so no such line is printed and the exit code is
not 0.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: rows of the seeded prefix that is also mined by the pure-python
#: reference (seconds on the host)
PRIME_PREFIX = 20_000
NOAC_PREFIX = 3_000
#: windows of the windowed runs
N_WINDOWS = 4
#: BibSonomy scale of the served table.  The serving index keeps every
#: cluster's membership words on the host, and their number grows about
#: as T^1.66 on this context: ~17.6M at scale 0.2 (2.2 GB resident on the
#: host), so the published scale would need tens of GB of host memory.
SERVE_SCALE = 0.25


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def device_check(chips: int) -> dict:
    """Phase a: the chip JAX sees, or SystemExit."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"devices: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices; JAX "
                         f"found {dev['count']}")
    return dev


def check_kernels_compile() -> None:
    """The three main-path kernels, compiled by Mosaic for this chip (a
    ``tpu_custom_call`` in the executable), never interpreted."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    t = 3000
    u32 = jax.ShapeDtypeStruct((t,), jnp.uint32)
    progs = {
        "segment_reduce": jax.jit(kops.segment_reduce).lower(
            u32, u32, jax.ShapeDtypeStruct((t,), jnp.bool_)),
        "radix_histogram": jax.jit(
            lambda a, b: kops.radix_histogram((a, b), (0, 8, 40),
                                              (8, 8, 8))).lower(u32, u32),
        "radix_rank": jax.jit(kops.radix_rank).lower(
            u32, jax.ShapeDtypeStruct((256,), jnp.int32)),
    }
    for name, lowered in progs.items():
        if "tpu_custom_call" not in lowered.compile().as_text():
            raise AssertionError(f"{name}: no Mosaic kernel in the "
                                 "compiled program")
    log("kernels compiled by Mosaic: " + ", ".join(progs))


def differing_leaves(a, b) -> list:
    """Names of the leaves of two ``PipelineResult``s that differ bit for
    bit."""
    import dataclasses

    import numpy as np
    return [f.name for f in dataclasses.fields(a)
            if not np.array_equal(np.asarray(getattr(a, f.name)),
                                  np.asarray(getattr(b, f.name)))]


def timed_mine(label: str, ctx, **params):
    """``core.mine`` once (compile + run) and once warm."""
    from repro.core import mine
    t0 = time.perf_counter()
    run = mine(ctx, **params)
    first = time.perf_counter() - t0
    run.rerun()
    log(f"{label}: {run.n_clusters} clusters; smoke timing: first call "
        f"{first:.1f} s (compile included), warm {run.rerun.last_s:.3f} s")
    return run


def mining_phase(name: str, ctx, prefix: int, **params) -> int:
    """Phases b and c: kernels vs windowed vs jnp path vs reference."""
    from repro.core import PolyadicContext, mine
    from repro.core import pipeline as P
    from repro.core.postprocess import cluster_set
    t = ctx.num_tuples
    kern = timed_mine(f"{name} kernels", ctx, backend="batch", **params)
    win = timed_mine(f"{name} windowed x{N_WINDOWS}", ctx, backend="batch",
                     window_budget=-(-t // N_WINDOWS), **params)
    jnp_ = timed_mine(f"{name} jnp path", ctx, backend="batch",
                      use_pallas=False, sort_backend="lax", **params)
    for other, label in ((jnp_, "jnp path"), (win, "windowed run")):
        diff = differing_leaves(kern.result, other.result)
        if diff:
            raise AssertionError(f"{name}: kernel result differs from the "
                                 f"{label} in {diff}")
    sigs = P.kept_sig_words(kern.result)
    if sigs.size != kern.n_clusters:
        raise AssertionError(f"{name}: {sigs.size} signatures for "
                             f"{kern.n_clusters} clusters")
    head = PolyadicContext(ctx.sizes, ctx.tuples[:prefix],
                           None if ctx.values is None
                           else ctx.values[:prefix])
    got = mine(head, backend="batch", **params)
    want = mine(head, backend="reference", **params)
    if cluster_set(got.clusters) != cluster_set(want.clusters):
        raise AssertionError(f"{name}: the first {prefix} rows mine "
                             "differently from the reference")
    log(f"{name}: kernels == windowed == jnp path ({kern.n_clusters} "
        f"clusters); first {prefix} rows == reference "
        f"({want.n_clusters} clusters)")
    return kern.n_clusters


def _contains(hit, row) -> bool:
    return all(int(e) in comp for e, comp in zip(row, hit["components"]))


def serving_phase(ctx) -> None:
    """Phase d: the streaming service behind the HTTP protocol."""
    import numpy as np

    from repro.core import mine
    from repro.serve.protocol import ClusterClient, make_server
    from repro.serve.service import TriclusterService

    expect_clusters = mine(ctx, backend="batch").n_clusters
    t0 = time.perf_counter()
    svc = TriclusterService(ctx.sizes, backend="streaming")
    chunks = 8
    step = -(-ctx.num_tuples // chunks)
    for lo in range(0, ctx.num_tuples, step):
        svc.add(ctx.tuples[lo:lo + step])
    svc.start()
    server = make_server(svc, host="127.0.0.1", port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        cl = ClusterClient(f"http://127.0.0.1:{server.port}")
        h = cl.wait_ready(timeout=600)
        log(f"serving: first snapshot v{h['version']} with "
            f"{cl.stats()['clusters']} clusters; smoke timing: preload "
            f"{chunks} chunks + first mine {time.perf_counter() - t0:.1f} s")
        if cl.stats()["clusters"] != expect_clusters:
            raise AssertionError("served cluster count differs from the "
                                 "batch mine of the same table")
        e0 = int(ctx.tuples[0, 0])
        scalar = cl.query(entity=e0, mode=0, k=5)
        if not scalar["hits"]:
            raise AssertionError(f"no cluster holds user {e0}")
        ents = [int(e) for e in np.unique(ctx.tuples[:64, 0])]
        batch = cl.query_batch(ents, mode=0, k=5)
        if batch["version"] == scalar["version"] and \
                batch["hits"][ents.index(e0)] != scalar["hits"]:
            raise AssertionError("batch and scalar queries disagree")
        top = cl.query(k=5, include_components=True)
        scores = [hit["score"] for hit in top["hits"]]
        if not scores or scores != sorted(scores, reverse=True):
            raise AssertionError(f"top-k not ranked: {scores}")
        by_sig = cl.query(signature=top["hits"][0]["signature"],
                          include_components=True)
        if by_sig["hits"][0]["components"] != \
                top["hits"][0]["components"]:
            raise AssertionError("signature lookup differs from top-k")
        log(f"serving: entity, batch ({len(ents)} users), top-k and "
            "signature queries agree")

        # a triple of the rarest ids: a cluster of its own once written
        row = [n - 1 for n in ctx.sizes]
        tw = time.perf_counter()
        if cl.upsert([row])["stream_version"] < 1:
            raise AssertionError("upsert not acknowledged")
        v_up = cl.refresh()["version"]
        seen = cl.query(entity=row[0], mode=0, k=1000,
                        at_least_version=v_up, timeout=600,
                        include_components=True)
        if not any(_contains(hit, row) for hit in seen["hits"]):
            raise AssertionError(f"upserted row {row} not served at "
                                 f"version {v_up}")
        cl.delete([row])
        v_del = cl.refresh()["version"]
        gone = cl.query(entity=row[0], mode=0, k=1000,
                        at_least_version=v_del, timeout=600,
                        include_components=True)
        if any(_contains(hit, row) for hit in gone["hits"]):
            raise AssertionError(f"deleted row {row} still served at "
                                 f"version {v_del}")
        stats = cl.stats()
        if stats["mine_errors"] or stats["publish_errors"]:
            raise AssertionError(f"service errors: {stats}")
        log(f"serving: upsert read back at v{v_up}, delete at v{v_del}; "
            f"smoke timing: write + refresh + read x2 "
            f"{time.perf_counter() - tw:.1f} s; mine_errors=0 "
            "publish_errors=0")
    finally:
        server.shutdown()
        server.server_close()
        svc.stop()
        thread.join(timeout=60)


def one_chip() -> None:
    from repro.data import synthetic
    check_kernels_compile()
    mining_phase("bibsonomy prime", synthetic.bibsonomy_like(seed=0),
                 PRIME_PREFIX)
    mining_phase("movielens noac",
                 synthetic.movielens_like(n_tuples=1_000_000, seed=0),
                 NOAC_PREFIX, variant="noac", delta=1.0)
    serving_phase(synthetic.bibsonomy_like(seed=0, scale=SERVE_SCALE))


def four_chips(n_tuples: int = 1_000_000) -> None:
    """``DistributedMiner`` over every chip vs ``BatchMiner`` on one."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import (BatchMiner, DistributedMiner, NOACMiner,
                            pad_tuples, pad_values)
    from repro.core import pipeline as P
    from repro.data import synthetic
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    n = mesh.shape["data"]
    ctx = synthetic.movielens_like(n_tuples=n_tuples, seed=0)
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    lane = NamedSharding(mesh, PartitionSpec("data"))
    calls = {}
    for variant, c, kw in (("prime", ctx, {}),
                           ("noac", ctx.deduplicated(), {"delta": 1.0})):
        calls[variant, "one chip"] = (
            (BatchMiner(c.sizes), c.tuples) if variant == "prime"
            else (NOACMiner(c.sizes, **kw), c.tuples, c.values))
        tuples = jax.device_put(pad_tuples(c.tuples, n), rows)
        values = jax.device_put(
            pad_values(c.values if c.values is not None
                       else np.zeros(c.num_tuples, np.float32), n), lane)
        for strategy in ("replicate", "shuffle"):
            dm = DistributedMiner(c.sizes, mesh, axes="data",
                                  strategy=strategy, **kw)
            calls[variant, strategy] = (dm, tuples, values)

    def timed(fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        return out, time.perf_counter() - t0

    # The six programs are independent, and XLA compiles them in threads
    # that release the GIL: the first calls take about as long as the
    # longest compile, not the sum of all six.
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(calls)) as pool:
        futures = {key: pool.submit(timed, *call)
                   for key, call in calls.items()}
        first = {key: f.result() for key, f in futures.items()}
    log(f"{len(calls)} programs compiled and run once; smoke timing: "
        f"{time.perf_counter() - t0:.1f} s (compile included)")
    for variant in ("prime", "noac"):
        want, _ = first[variant, "one chip"]
        if len(want.sig_lo.sharding.device_set) != 1:
            raise AssertionError("the single-device run was sharded")
        want_sigs = P.kept_sig_words(want)
        for strategy in ("replicate", "shuffle"):
            dm, tuples, values = calls[variant, strategy]
            got, warm = timed(dm, tuples, values)
            spans = {len(a.sharding.device_set)
                     for a in (tuples, values, got.sig_lo, got.keep)}
            if spans != {n}:
                raise AssertionError(f"inputs/results span {spans} "
                                     f"devices, not {n}")
            sigs = P.kept_sig_words(got)
            if not np.array_equal(sigs, want_sigs) or not np.array_equal(
                    sigs, P.kept_sig_words(first[variant, strategy][0])):
                raise AssertionError(
                    f"{variant}/{strategy}: {sigs.size} clusters on {n} "
                    f"chips vs {want_sigs.size} on one")
            log(f"movielens {variant} {strategy} on {n} chips == one chip "
                f"({sigs.size} clusters); smoke timing: first call "
                f"{first[variant, strategy][1]:.1f} s (compile included, "
                f"concurrent), warm {warm:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the distributed path, on four chips")
    args = ap.parse_args(argv)
    dev = device_check(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    peak_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; peak "
        f"host memory {peak_gb:.1f} GiB")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
