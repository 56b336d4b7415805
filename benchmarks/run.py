"""Benchmark runner: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (+ the distributed mesh benchmark).
``--scale`` shrinks dataset sizes to the CPU budget (default settings
finish in a few minutes on one core); every run saves raw JSON under
results/, plus a machine-readable ``BENCH_mining.json`` summary with
per-backend/variant wall-time and tuples/sec so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def _mining_summary(results: dict, scale: float) -> dict:
    """Normalise each job's raw output to rows of
    {backend, variant, dataset, n_tuples, ms, tuples_per_s}."""
    rows = []

    def row(backend, variant, dataset, n, ms, **extra):
        if not n or ms is None:
            return
        rows.append({"backend": backend, "variant": variant,
                     "dataset": dataset, "n_tuples": int(n),
                     "ms": float(ms),
                     "tuples_per_s": float(n) / (float(ms) / 1e3)
                     if ms else 0.0, **extra})

    for r in (results.get("table4") or {}).values():
        row("batch", "prime", "movielens-like", r["tuples"], r["total_ms"])
    for r in (results.get("scaling") or {}).get("fig2", []):
        row("batch", "prime", "movielens-like", r["n"], r["ms"])
    for r in (results.get("scaling") or {}).get("fig3", []):
        row("batch", "noac", "frames-like", r["n"], r["ms"],
            params=r.get("params"))
    for r in (results.get("scaling") or {}).get("noac_distributed", []):
        row("distributed", "noac", "frames-like", r["n"], r["ms"],
            strategy=r["strategy"])
    for r in (results.get("scaling") or {}).get("streaming", []):
        row("streaming", "prime", "movielens-like", r["n"],
            r["mean_snapshot_ms"], mode=r["mode"],
            snapshots=r["snapshots"])
    for r in (results.get("table5") or []):
        row("batch", "noac", "frames-like", r["n"], r["par_ms"])
        row("reference", "noac", "frames-like", r["n"], r["seq_ms"])
    for r in (results.get("packed") or {}).get("rows", []):
        row(r["backend"], r["variant"], r["dataset"], r["n_tuples"],
            r["ms"],
            **{k: r[k] for k in ("sort_path", "stages", "radix", "mode")
               if k in r})
    dist = results.get("distributed") or {}
    for strategy in ("replicate", "shuffle"):
        for variant, key in (("prime", strategy), ("noac",
                                                   f"noac_{strategy}")):
            d = dist.get(key)
            if d:
                n = (dist.get("noac_n_tuples") if variant == "noac"
                     else dist.get("n_tuples"))  # noac mines deduplicated
                row("distributed", variant, "movielens-like", n, d["ms"],
                    strategy=strategy, devices=8)
    out = {"scale": scale, "rows": rows}
    if results.get("packed"):
        # headline sort-path ratios (Stage-1 sort and end-to-end),
        # movielens-like, both variants: lexsort vs the packed default
        # and packed-lax vs packed-radix (the comparison-sort swap)
        out["packed_speedup"] = results["packed"]["speedup"]
        out["radix_speedup"] = results["packed"]["radix_speedup"]
        # run-store ratios (out-of-core overhead, incremental snapshot
        # gain) + the fixed machine-speed probe for cross-PR
        # normalisation (ROADMAP benchmark hygiene)
        out["runs_speedup"] = results["packed"]["runs_speedup"]
        out["calibration"] = results["packed"]["calibration"]
        # windowed device pipeline (DESIGN.md §3c): bit-identity +
        # equal-T throughput + peak-allocation ratios, schema-gated by
        # benchmarks/validate.py (older raw docs lack the section)
        if results["packed"].get("windowed"):
            out["windowed"] = results["packed"]["windowed"]
    if results.get("serving"):
        # online query service: latency under a write trickle, swap
        # staleness, batch-vs-scalar speedup (benchmarks/serving.py);
        # the sharded-plane results (delta index rebuild, replica
        # scale-out) are their own gated section
        srv = dict(results["serving"])
        scale_sec = srv.pop("serving_scale", None)
        obs_sec = srv.pop("serving_obs", None)
        out["serving"] = srv
        if scale_sec:
            out["serving_scale"] = scale_sec
        # observability instrumentation overhead (DESIGN.md §11):
        # metrics-on vs metrics-off query p50 and snapshot-swap
        # latency, gated <= 3% at report scale by validate.py
        if obs_sec:
            out["serving_obs"] = obs_sec
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.12,
                    help="dataset size multiplier vs the paper's")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--only", default="",
                    help="comma list: table3,table4,table5,scaling,"
                    "distributed,packed,serving")
    ap.add_argument("--out", default="BENCH_mining.json",
                    help="summary filename under results/ (smoke runs "
                    "should not overwrite the tracked full-scale file)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import distributed, packed, scaling, serving, table3, table4, \
        table5
    from .common import save_json
    n_dist = int(320_000 * args.scale)
    jobs = {
        "table3": lambda: table3.run(scale=args.scale * 3,
                                     repeat=args.repeat),
        "table4": lambda: table4.run(scale=args.scale, repeat=args.repeat),
        "table5": lambda: table5.run(scale=args.scale / 2,
                                     repeat=args.repeat),
        "scaling": lambda: scaling.run(scale=args.scale,
                                       repeat=args.repeat),
        "distributed": lambda: distributed.run(n_tuples=n_dist),
        "packed": lambda: packed.run(scale=args.scale, repeat=args.repeat),
        "serving": lambda: serving.run(scale=args.scale,
                                       repeat=args.repeat),
    }
    only = [s for s in args.only.split(",") if s] or list(jobs)
    rc = 0
    results = {}
    for name in only:
        print(f"\n######## {name} ########", flush=True)
        try:
            results[name] = jobs[name]()
        except Exception:
            traceback.print_exc()
            rc = 1
    if results.get("distributed") is not None:
        results["distributed"]["n_tuples"] = n_dist
    path = save_json(args.out, _mining_summary(results, args.scale))
    print(f"\n[bench] wrote {path}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
