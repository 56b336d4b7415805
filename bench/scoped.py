"""One traced run of one benchmark cell, reduced by the program's own
names: device time per named stage scope, host time per ``repro.*``
phase, idle gaps labelled by the innermost host span, and the readers
of ``benchlib/scopes.py`` beside the cell's own per-layer metrics.

    python bench/scoped.py --workload <cell> --seed <n> --seconds <s> \\
        [--excerpt <path>] [--keep-trace <path>]

The run is the harness's ``--trace 1`` run (same driver, same window,
same check against the reference) with ``bench.gc`` spans on; only the
reduction differs.  The last line of standard output is one JSON
object; ``--excerpt`` also writes the operations, scopes and host spans
of two warm mines from the middle of the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness, scopes  # noqa: E402
from benchlib.compare import judge  # noqa: E402
from benchlib import trace as TR  # noqa: E402

#: per-layer readers of ``benchlib/scopes.py``
SCOPED_READERS = ("stage1_sort_ms_per_mine.mine",
                  "stage2_components_ms_per_mine.mine",
                  "delta_search_ms_per_mine.mine",
                  "stage3_dedup_ms_per_mine.mine",
                  "host_prep_ms_per_mine.mine")


def excerpt(trace: dict, facts: dict, mines: int = 2) -> dict:
    """The events of ``mines`` consecutive warm mines from the middle
    of the window: from the start of one ``bench.mine`` span to the end
    of the ``bench.fetch`` after the last, times relative to its start."""
    spans = sorted((s for s in trace["host"] if s[0] == "bench.mine"),
                   key=lambda s: s[1])
    fetches = sorted((s for s in trace["host"] if s[0] == "bench.fetch"),
                     key=lambda s: s[1])
    k = max(0, len(spans) // 2 - mines // 2)
    t0 = spans[k][1]
    last = spans[k + mines - 1]
    t1 = next(s[1] + s[2] for s in fetches if s[1] >= last[1])
    dev = sorted(trace["devices"])[0]
    keep = [i for i, e in enumerate(trace["devices"][dev])
            if t0 <= e[1] and e[1] + e[2] <= t1]
    events = [[e[0], e[1] - t0, e[2]] for e in
              (trace["devices"][dev][i] for i in keep)]
    host = [[n, s - t0, d] for n, s, d in trace["host"]
            if t0 <= s and s + d <= t1]
    out = {"devices": {"0": events},
           "scopes": {"0": [trace["scopes"][dev][i] for i in keep]},
           "host": host, "window_s": (t1 - t0) / 1e9,
           "facts": dict(facts, mines=mines)}
    out["busy_s"] = TR.busy_ns(events) / 1e9
    return out


def reduce_run(job, out, peaks, planes=("/device:TPU:", TR.OPS_LINE),
               excerpt_path=None, keep_trace=None) -> dict:
    """The result of a traced run whose window has ended: ``planes`` are
    the prefixes of the plane and line names of the device's
    operations; ``keep_trace`` is where to copy the trace file to."""
    tdir = job.window.trace_dir
    try:
        path = harness.trace_file(tdir)
        if keep_trace:
            shutil.copy(path, keep_trace)
        t_load = time.perf_counter()
        trace = scopes.load(path, [str(i) for i in range(len(job.devices))],
                            *planes)
        load_s = time.perf_counter() - t_load
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    summary = scopes.summarize(trace, job.window_s)
    facts, mines = out.facts, out.facts["mines"]
    readers = dict(job.readers)
    readers.update({m: harness.load_module("metrics", m, job.root)
                    for m in SCOPED_READERS})
    metrics = {m: mod.read(summary, facts, peaks)
               for m, mod in readers.items()}
    stages = scopes.stage_seconds(summary)
    unscoped = {}
    dev = sorted(summary["devices"])[0]
    for (name, _, d), op in zip(summary["devices"][dev],
                                summary["scopes"][dev]):
        if not scopes.stage_of(op):
            key = (op or name.split(" = ")[0])[:80]
            unscoped[key] = unscoped.get(key, 0.0) + float(d) / 1e9
    spans = {}
    for name, _, d in summary["host"]:
        n, s = spans.get(name, (0, 0.0))
        spans[name] = (n + 1, s + float(d) / 1e9)
    if excerpt_path:
        with open(excerpt_path, "w") as f:
            json.dump(excerpt(trace, facts), f)
    return {
        "workload": job.workload, "seed": job.seed, "device": job.device,
        "correct": judge(out.numbers, job.limits)[0],
        "mines": mines, "rows": facts["rows"], "window_s": job.window_s,
        "traced_tuples_per_s": facts["rows"] * mines / job.window_s,
        "busy_s": summary["busy_s"], "load_s": load_s,
        "metrics": metrics,
        "stage_ms_per_mine": {k or "(none)": 1e3 * v / mines
                              for k, v in sorted(stages.items())},
        "scoped_share_of_busy": sum(v for k, v in stages.items() if k)
        / summary["busy_s"],
        "unscoped_top_ms": sorted(((k, 1e3 * v) for k, v in
                                   unscoped.items()),
                                  key=lambda kv: -kv[1])[:12],
        "host_span_ms_per_mine": {k: 1e3 * s / mines
                                  for k, (n, s) in sorted(spans.items())},
        "host_span_counts": {k: n for k, (n, s) in sorted(spans.items())},
        "idle_gaps": summary["idle_gaps"],
        "device_ops": summary["device_ops"][:5],
    }


def run(job, **kw) -> dict:
    """Drive one traced run of ``job`` with ``bench.gc`` spans on; its
    reduced result (``kw`` go to ``reduce_run``)."""
    job.hold_devices()
    sys.path.insert(0, str(job.root.parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    job.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    peaks = harness.load_peaks(job.device["kind"], job.root)
    with job.counting_compiles(), scopes.gc_spans():
        out = job.driver.run(job)
    return reduce_run(job, out, peaks, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=10)
    ap.add_argument("--excerpt")
    ap.add_argument("--keep-trace", help="copy the .xplane.pb here")
    args = ap.parse_args(argv)
    if args.excerpt:
        os.makedirs(os.path.dirname(os.path.abspath(args.excerpt)),
                    exist_ok=True)
    job = harness.Job(args.workload, args.seed, args.seconds, True, T_START)
    result = run(job, excerpt_path=args.excerpt, keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
