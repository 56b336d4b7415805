"""Helpers of the benchmark's tests: a copy of ``bench/`` with a tiny
cell, and a run of it on the CPU with the look for a chip skipped."""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

#: tiny tables of the two mine configurations
TINY_TABLES = {
    "bibsonomy-prime": {"generator": "bibsonomy_like", "scale": 0.004},
    "movielens1m-noac": {"generator": "ratings", "users": 60, "movies": 40,
                         "rated_movies": 37,
                         "star_counts": [20, 40, 90, 110, 80],
                         "user_floor": 2, "user_top": 30,
                         "movie_alpha": 0.43},
}


def copy_bench(dest: Path) -> Path:
    """A checkout-like copy: ``<dest>/BENCHMARK.json``, ``<dest>/bench``
    and a link to the program's ``src``."""
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest)
    os.symlink(REPO / "src", dest / "src")
    return dest / "bench"


def add_cell(root: Path, cell: str, name: str, config: str = None) -> str:
    """Declare ``name`` in the copy's BENCHMARK.json as a copy of
    ``cell`` (over ``config`` where given), reporting what ``cell``
    reports, with ``cell``'s limits."""
    path = root.parent / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    spec = dict(next(w for w in bench["workloads"] if w["name"] == cell))
    spec.update(name=name, config=config or spec["config"])
    bench["workloads"].append(spec)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if cell in m.get("workloads", ()):
            m["workloads"].append(name)
    path.write_text(json.dumps(bench))
    shutil.copy(root / "cells" / f"{cell}.json",
                root / "cells" / f"{name}.json")
    return name


def tiny_cell(root: Path, cell: str) -> str:
    """``tiny.<cell>``: ``cell`` over a tiny table of its configuration."""
    config = next(w["config"] for w in json.loads(
        (root.parent / "BENCHMARK.json").read_text())["workloads"]
        if w["name"] == cell)
    cfg = json.loads((root / "configs" / f"{config}.json").read_text())
    cfg["table"] = TINY_TABLES[config]
    (root / "configs" / f"tiny.{config}.json").write_text(json.dumps(cfg))
    return add_cell(root, cell, f"tiny.{cell}", f"tiny.{config}")


def run_on_cpu(root: Path, workload: str, seed: int = 2**31 + 9,
               seconds: int = 1) -> dict:
    """A whole run of ``workload`` on the CPU, the look for a chip
    skipped; the parsed result line."""
    from benchlib import harness
    import jax
    job = harness.Job(workload, seed, seconds, False, time.perf_counter(),
                      root=root)

    def hold():
        job.device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
        job.devices = jax.devices()[:1]
        return job.device
    job.hold_devices = hold
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err), \
            _cache_config_restored(root.parent / ".jax_cache"):
        assert harness.run(job) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@contextlib.contextmanager
def _cache_config_restored(cache_dir: Path):
    """The run turns on the persistent compile cache, here in the copy;
    the other tests of this worker get the process's settings back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    try:
        yield
    finally:
        if env is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
