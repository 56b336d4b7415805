"""Closed-loop sharded mining: one whole-table mine after another over
the cell's chips.

Traffic parameters: ``backend``, the engine ``repro.core.mine`` runs
(``distributed``), and ``options``, further keyword arguments of
``mine`` (``strategy``).  The mesh is one ``data`` axis over the cell's
devices; the engine block-shards the table by rows, padded to a
multiple of the chips with copies of its first row.

One mine is the entry the window drives: ``MineRun.rerun()`` (the host
table copied in, the sharded program, the wait for its dropped-record
count) and the fetch of the result the comparison reads
(``bigreference.FIELDS``).  The window lasts at least ``--seconds`` and
ends when the mine running at that moment has been fetched.  Overflow
retries, if any, happen before the window: the capacity they settle on
stays.  After the window the last mine's result over the table's own
rows is compared with the plain reference of ``benchlib/bigreference.py``
and with the window's first mine.  The mine passes an enabled
``repro.obs`` hub where the program takes one, for the shuffle's
counters (``shuffle_slot_fill``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchlib import bigratings, bigreference, tables  # noqa: F401
from benchlib.harness import Outcome

#: the program's counters of the records routed and the owner slots
#: sorted, summed over modes into the facts the readers take
COUNTERS = {"shuffle_records": "distributed_shuffle_records_total",
            "shuffle_slots": "distributed_shuffle_slots_total"}


def fetch(result, rows: int) -> dict:
    """The compared fields over the table's own ``rows``, and the
    result's dropped-record count."""
    out = {f: np.asarray(getattr(result, f))[..., :rows]
           for f in bigreference.FIELDS}
    out["overflow"] = int(np.asarray(result.overflow))
    return out


def counter_totals(obs) -> dict:
    """{fact: summed value} of the program's shuffle counters; a
    program without them gives none."""
    doc = obs.metrics.to_dict()
    return {fact: sum(s["value"] for s in doc[name]["series"])
            for fact, name in COUNTERS.items() if name in doc}


def bounds_paths(obs) -> dict:
    """{path: count} of the program's δ-window paths (its owners')."""
    doc = obs.metrics.to_dict().get("pipeline_delta_bounds_total")
    return {s["labels"]["path"]: s["value"]
            for s in (doc or {"series": []})["series"]}


def run(job) -> Outcome:
    from jax.sharding import Mesh
    from repro.core import PolyadicContext, mine
    from repro.obs import Obs
    cfg = job.config
    params = dict(cfg["mine"])
    obs = Obs.create()
    with job.phase("data"):
        sizes, tuples, values = tables.make_table(cfg["table"], job.seed)
        ctx = PolyadicContext(sizes, tuples, values)
        if ctx.tuples.shape != tuples.shape:
            raise RuntimeError("the program reshaped the canonical table")
    t = tuples.shape[0]
    mesh = Mesh(np.asarray(job.devices), ("data",))
    with job.phase("compile_or_cache"):
        run_ = mine(ctx, backend=job.traffic["backend"], mesh=mesh, obs=obs,
                    **params, **job.traffic.get("options", {}))
    with job.phase("warmup"):
        fetch(run_.rerun(), t)
    mines, first, last, overflow = 0, None, None, 0
    with job.measure() as window:
        while True:
            with job.span("bench.mine"):
                res = run_.rerun()
            with job.span("bench.fetch"):
                out = fetch(res, t)
            mines += 1
            overflow += out.pop("overflow")
            first = out if first is None else first
            last = out
            if window.expired():
                break
    peak = job.memory_peak()
    facts = {"memory_peak_bytes": peak, "mines": mines, "rows": t,
             **counter_totals(obs)}
    del run_, res, ctx
    gc.collect()
    t0 = time.perf_counter()
    want = bigreference.mine_config(params, tuples, values)
    reference_s = time.perf_counter() - t0
    numbers = bigreference.numbers(last, want)
    numbers["repeat_mismatch"] = bigreference.repeat_mismatch(first, last)
    numbers["overflow"] = overflow
    return Outcome(
        attempted=mines, failed=0,
        end_to_end={"mine_tuples_per_s": t * mines / window.elapsed},
        numbers=numbers, facts=facts,
        log={"rows": t, "mines": mines, "reference_s": reference_s,
             "kept_clusters": int(np.count_nonzero(last["keep"])),
             "delta_bounds": bounds_paths(obs)})
