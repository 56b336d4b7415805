"""Closed-loop mining: one whole-table mine after another.

Traffic parameters: ``backend``, the engine ``repro.core.mine`` runs,
and ``options``, further keyword arguments of ``mine`` that change how
the program mines but not what (``window_budget``, ``chunk_budget``).

One mine is the entry the window drives: ``MineRun.rerun()`` of
``repro.core.mine`` (the host table copied in, Stages 1-3 on the
device), then the fetch of the compact result that materialisation
reads (``keep``, ``density``, ``range_lo``, ``range_hi``,
``sorted_e``).  The window lasts at least ``--seconds`` and ends when
the mine running at that moment has been fetched, so every mine counted
finished inside it and the rate covers all of its time.  After the
window, the last mine's result is compared with the plain reference
over the same table, and with the window's first mine.
"""
from __future__ import annotations

import gc

import numpy as np

from benchlib import compare, reference, tables, work
from benchlib.harness import Outcome


def fetch(result) -> dict:
    return {f: np.asarray(getattr(result, f)) for f in compare.FIELDS}


def run(job) -> Outcome:
    from repro.core import PolyadicContext, mine
    cfg = job.config
    params = dict(cfg["mine"])
    backend = job.traffic["backend"]
    with job.phase("data"):
        sizes, tuples, values = tables.make_table(cfg["table"], job.seed)
        ctx = PolyadicContext(sizes, tuples, values)
        if ctx.tuples.shape != tuples.shape:
            raise RuntimeError("the program reshaped the canonical table")
    with job.phase("compile_or_cache"):
        run_ = mine(ctx, backend=backend, **params,
                    **job.traffic.get("options", {}))
    with job.phase("warmup"):
        fetch(run_.rerun())
    mines, first, last = 0, None, None
    with job.measure() as window:
        while True:
            with job.span("bench.mine"):
                res = run_.rerun()
            with job.span("bench.fetch"):
                out = fetch(res)
            mines += 1
            first = out if first is None else first
            last = out
            if window.expired():
                break
    t = tuples.shape[0]
    peak = job.memory_peak()
    del run_, res, ctx
    gc.collect()
    want = reference.mine_config(params, tuples, values)
    numbers = compare.mine_numbers(last, want)
    numbers["repeat_mismatch"] = compare.repeat_mismatch(first, last)
    slots = None if values is None else int(np.unique(values).size)
    facts = {"memory_peak_bytes": peak, "mines": mines, "rows": t,
             "radix_bytes_per_mine": work.radix_bytes_per_mine(t, sizes, slots),
             "segment_reduce_bytes_per_mine":
                 work.segment_reduce_bytes_per_mine(t, len(sizes))}
    return Outcome(
        attempted=mines, failed=0,
        end_to_end={"mine_tuples_per_s": t * mines / window.elapsed},
        numbers=numbers, facts=facts,
        log={"rows": t, "mines": mines,
             "kept_clusters": int(np.count_nonzero(last["keep"]))})
