"""The harness: finds a workload's configuration, traffic, driver,
limits and metric readers by name, holds the chip, times set-up and the
window, counts compiles, reads memory and the profiler trace, and
prints the result.

``BENCHMARK.json`` at the root of the checkout names each workload's
``config``, ``traffic`` and ``chips``, and which end-to-end and
per-layer metrics it reports.  Under ``bench/`` everything else is found
by file name:

* ``configs/<config>.json`` — a deployment: ``source``, ``table``,
  ``mine``, ``reduced``, ``assumed``.
* ``traffic/<traffic>.json`` — a traffic mix: its parameters, and the
  ``driver`` that runs it.
* ``drivers/<driver>.py`` — ``run(job) -> Outcome``.
* ``cells/<workload>.json`` — the ``limits`` of the numbers compared.
* ``metrics/<metric>.py`` — a per-layer reader:
  ``read(trace, facts, peaks) -> number or None``.
* ``peaks.json`` — published peaks by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parents[1]

#: the jax.monitoring event of a program being lowered: one per program
#: that is compiled or loaded from the persistent cache
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_json(kind: str, name: str, root: Path = BENCH) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no {kind} file named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = BENCH) -> dict:
    path = root.parent / "BENCHMARK.json"
    if not path.is_file():
        raise SystemExit(f"no BENCHMARK.json beside {root}")
    with open(path) as f:
        return json.load(f)


def list_cells(root: Path = BENCH) -> list:
    return sorted(w["name"] for w in load_benchmark(root)["workloads"])


def cell_metrics(bench: dict, workload: str) -> tuple[dict, dict]:
    """({end-to-end metric: entry}, {per-layer metric: entry}) that
    ``workload`` reports: a metric with a ``workloads`` list where it
    lists the workload; an end-to-end metric without one everywhere; a
    per-layer metric without one wherever the metric it moves is
    reported."""
    e2e = {m["name"]: m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])}
    layer = {m["name"]: m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in e2e}
    return e2e, layer


def load_module(kind: str, name: str, root: Path = BENCH):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = root / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(kind: str, root: Path = BENCH) -> dict:
    with open(root / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


@dataclasses.dataclass
class Outcome:
    """What a traffic module hands back after its window."""
    attempted: int
    failed: int
    end_to_end: dict          # metric name -> value (host clock)
    numbers: dict             # compared number name -> value
    facts: dict               # what the per-layer readers need
    log: dict = dataclasses.field(default_factory=dict)


class Window:
    """The measured window: at least ``seconds`` long; traced when the
    run is a ``--trace 1`` run; compiles inside it are counted."""

    def __init__(self, job: "Job"):
        self.job = job
        self.t0 = self.t1 = None
        self.compiles0 = 0
        self.trace_dir = None

    def __enter__(self):
        job = self.job
        job.setup_s = time.perf_counter() - job.t_start
        job.log(f"setup_s={job.setup_s:.3f} split: " + " ".join(
            f"{k}={v:.3f}" for k, v in job.phases.items()))
        if job.trace:
            import jax
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        self.compiles0 = job.compiles
        self.t0 = time.perf_counter()
        return self

    def expired(self) -> bool:
        return time.perf_counter() - self.t0 >= self.job.seconds

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        job = self.job
        if self.trace_dir is not None:
            import jax
            jax.profiler.stop_trace()
        job.window_s = self.t1 - self.t0
        job.compiles_in_window = job.compiles - self.compiles0
        job.log(f"window_s={job.window_s:.3f} compiles_in_window="
                f"{job.compiles_in_window}")
        return False

    @property
    def elapsed(self) -> float:
        return (self.t1 or time.perf_counter()) - self.t0


class Job:
    """One run of one cell."""

    def __init__(self, workload: str, seed: int, seconds: int, trace: bool,
                 t_start: float, root: Path = BENCH):
        self.workload, self.seed = workload, int(seed)
        self.seconds, self.trace = int(seconds), bool(trace)
        self.t_start = t_start
        self.root = root
        bench = load_benchmark(root)
        spec = {w["name"]: w for w in bench["workloads"]}.get(workload)
        if spec is None:
            raise SystemExit(f"no workload named {workload!r} in "
                             f"BENCHMARK.json")
        self.spec = spec
        self.limits = load_json("cells", workload, root)["limits"]
        self.config = load_json("configs", spec["config"], root)
        self.traffic = load_json("traffic", spec["traffic"], root)
        self.driver = load_module("drivers", self.traffic["driver"], root)
        e2e, layer = cell_metrics(bench, workload)
        self.end_to_end = sorted(e2e)
        self.units = {n: m["unit"] for n, m in {**e2e, **layer}.items()}
        self.readers = {m: load_module("metrics", m, root) for m in layer}
        self.phases = {}
        self.compiles = 0
        self.device = None
        self.tag = "[bench]"
        self.setup_s = self.window_s = None
        self.compiles_in_window = None
        self.window = None

    def log(self, msg: str) -> None:
        print(f"{self.tag} {msg}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        """A named part of set-up, timed on the host clock."""
        t0 = time.perf_counter()
        yield
        self.phases[name] = self.phases.get(name, 0.0) + \
            time.perf_counter() - t0

    def span(self, name: str):
        """A host span in the profiler's trace (nothing when untraced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def measure(self) -> Window:
        self.window = Window(self)
        return self.window

    # -- the chip ----------------------------------------------------------

    def hold_devices(self) -> dict:
        """The devices JAX sees; SystemExit (no result) unless they are
        at least the cell's chips of a TPU."""
        import jax
        devs = jax.devices()
        chips = int(self.spec["chips"])
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        self.tag = f"[bench {dev['platform']} {dev['kind']!r} x{dev['count']}]"
        self.log(f"platform={dev['platform']} device_kind={dev['kind']!r} "
                 f"device_count={dev['count']}")
        if dev["platform"] != "tpu":
            raise SystemExit(f"{self.tag} no TPU: JAX found platform "
                             f"{dev['platform']!r}")
        if dev["count"] < chips:
            raise SystemExit(f"{self.tag} the cell needs {chips} chips; JAX "
                             f"found {dev['count']}")
        self.device = dev
        self.devices = devs[:chips]
        return dev

    @contextlib.contextmanager
    def counting_compiles(self):
        """Count the programs lowered while the block runs."""
        import jax

        def listener(event, duration, **kw):
            if event == LOWERING_EVENT:
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            yield
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)

    def memory_peak(self) -> Optional[int]:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None


def trace_file(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_trace(job: Job, out: Outcome, peaks: dict):
    """(per-layer metrics, device busy/window, breakdown) of a traced
    run; the trace directory is removed afterwards."""
    from . import trace as TR
    tdir = job.window.trace_dir
    try:
        path = trace_file(tdir)
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        events = TR.load_xplane(path, [str(i) for i in
                                       range(len(job.devices))])
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    summary = TR.summarize(events, job.window_s)
    job.log("kernel_ops=" + json.dumps(TR.kernel_totals(events)))
    metrics = {}
    for name, mod in job.readers.items():
        value = mod.read(summary, out.facts, peaks)
        if value is not None:
            metrics[name] = value
    return metrics, summary


def run(job: Job) -> int:
    """Drive one run and print its result line; the exit code."""
    job.hold_devices()
    sys.path.insert(0, str(job.root.parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    # every program of the cell goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    job.log(f"compile cache: {cache}")
    peaks = load_peaks(job.device["kind"], job.root)
    with job.counting_compiles():
        out = job.driver.run(job)
    device = dict(job.device)
    device["count"] = len(job.devices)
    device["memory_peak_bytes"] = out.facts.get("memory_peak_bytes")
    job.log(f"peak_bytes_in_use={device['memory_peak_bytes']}")
    for k, v in out.log.items():
        job.log(f"{k}={v}")
    result = {"correct": None, "attempted": out.attempted,
              "failed": out.failed, "metrics": {}, "device": device}
    units = job.units
    if job.trace:
        layer, summary = reduce_trace(job, out, peaks)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in layer.items()}
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        job.log(f"busy_s={summary['busy_s']} window_s="
                f"{summary['window_s']}")
    else:
        e2e = dict(out.end_to_end)
        e2e["setup_s"] = job.setup_s
        result["metrics"] = {k: {"value": e2e[k], "unit": units[k]}
                             for k in job.end_to_end}
    for k, v in result["metrics"].items():
        job.log(f"metric {k}={v['value']} {v['unit']}")
    from .compare import judge
    ok, table = judge(out.numbers, job.limits)
    result["correct"] = ok
    result["checks"] = table
    for name, (value, limit) in table.items():
        job.log(f"check {name} {value} limit {limit}")
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
