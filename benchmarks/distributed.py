"""Distributed engine benchmark: replicate vs shuffle merge on a real
multi-device host mesh (the paper's §1 centralise-vs-replicate trade).

A CPU rehearsal: runs in a subprocess on the CPU backend with 8 forced
host devices (the parent process has already locked jax to its own
devices); reports per-strategy wall time and the collective schedule
from the lowered HLO.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import print_table, save_json

_WORKER = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np
import jax
from repro.core import (BatchMiner, DistributedMiner, NOACMiner, pad_tuples,
                        pad_values)
from repro.data import synthetic
from repro.launch.mesh import make_mesh
from repro.analysis.hlo import profile_module

ctx = synthetic.movielens_like(n_tuples=int(%(n)d), seed=0)
mesh = make_mesh((8,), ("data",))
tuples = pad_tuples(ctx.tuples, 8)
out = {}
bm = BatchMiner(ctx.sizes)
r = bm(tuples); jax.block_until_ready(r.sig_lo)
t0 = time.perf_counter(); r = bm(tuples); jax.block_until_ready(r.sig_lo)
out["batch_1dev_ms"] = (time.perf_counter() - t0) * 1e3
for strategy in ("replicate", "shuffle"):
    dm = DistributedMiner(ctx.sizes, mesh, axes="data", strategy=strategy)
    r = dm(tuples); jax.block_until_ready(r.sig_lo)
    t0 = time.perf_counter(); r = dm(tuples); jax.block_until_ready(r.sig_lo)
    ms = (time.perf_counter() - t0) * 1e3
    prof = profile_module(dm.lowered(tuples).compile().as_text(), 8)
    out[strategy] = {"ms": ms,
                     "n_clusters": int(np.asarray(r.is_unique).sum()),
                     "overflow": int(getattr(r, "overflow", 0)),
                     "collectives": {k: list(v)
                                     for k, v in prof.by_kind.items()},
                     "coll_operand_bytes": prof.operand_bytes,
                     "coll_wire_bytes": prof.wire_bytes}
# NOAC (many-valued) through the same distributed pipeline
vctx = synthetic.movielens_like(n_tuples=int(%(n)d), seed=0,
                                values=True).deduplicated()
out["noac_n_tuples"] = int(vctx.num_tuples)
vt = pad_tuples(vctx.tuples, 8); vv = pad_values(vctx.values, 8)
nm = NOACMiner(vctx.sizes, delta=1.0)
r = nm(vt, vv); jax.block_until_ready(r.sig_lo)
t0 = time.perf_counter(); r = nm(vt, vv); jax.block_until_ready(r.sig_lo)
out["noac_batch_1dev_ms"] = (time.perf_counter() - t0) * 1e3
for strategy in ("replicate", "shuffle"):
    dm = DistributedMiner(vctx.sizes, mesh, axes="data", strategy=strategy,
                          delta=1.0)
    r = dm(vt, vv); jax.block_until_ready(r.sig_lo)
    t0 = time.perf_counter(); r = dm(vt, vv); jax.block_until_ready(r.sig_lo)
    out["noac_" + strategy] = {
        "ms": (time.perf_counter() - t0) * 1e3,
        "n_clusters": int(np.asarray(r.is_unique).sum()),
        "overflow": int(getattr(r, "overflow", 0))}
print("RESULT " + json.dumps(out))
'''


def run(n_tuples: int = 40_000):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the worker is a CPU rehearsal: it never reaches for a chip the
    # parent may hold
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(root, "src")
           + os.pathsep + os.environ.get("PYTHONPATH", "")}
    proc = subprocess.run([sys.executable, "-c", _WORKER % {"n": n_tuples}],
                          capture_output=True, text=True, env=env,
                          timeout=1200)
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            out = json.loads(line[len("RESULT "):])
    if not out:
        print(proc.stdout[-2000:])
        print(proc.stderr[-2000:])
        raise RuntimeError("distributed benchmark worker failed")
    rows = [["batch (1 dev)", f"{out['batch_1dev_ms']:.1f}", "-", "-"]]
    for s in ("replicate", "shuffle"):
        d = out[s]
        rows.append([s, f"{d['ms']:.1f}", f"{d['n_clusters']:,}",
                     f"{d.get('coll_wire_bytes', 0) / 1e6:.2f}MB"])
    rows.append(["noac batch (1 dev)", f"{out['noac_batch_1dev_ms']:.1f}",
                 "-", "-"])
    for s in ("replicate", "shuffle"):
        d = out[f"noac_{s}"]
        rows.append([f"noac {s}", f"{d['ms']:.1f}", f"{d['n_clusters']:,}",
                     "-"])
    print_table(f"Distributed mining, 8-device mesh, |I|={n_tuples:,}",
                ["engine", "ms", "#clusters", "collective wire"], rows)
    save_json("distributed.json", out)
    return out


if __name__ == "__main__":
    run()
