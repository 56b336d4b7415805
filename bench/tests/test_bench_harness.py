"""The benchmark harness on the CPU: every cell resolves by name, the
tables are deterministic in the seed and pinned to the program's
generators, and a run without a TPU fails without a result."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from bench_testutil import (BENCH, REPO, TINY_TABLES, add_cell,
                            copy_bench)

from benchlib import harness, tables

CELLS = harness.list_cells()
BENCHMARK = harness.load_benchmark()
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_existing_parts(cell):
    spec = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    cfg = harness.load_json("configs", spec["config"])
    assert {"source", "table", "mine", "reduced", "assumed"} <= set(cfg)
    assert spec["chips"] in (1, 4)
    traffic = harness.load_json("traffic", spec["traffic"])
    assert callable(harness.load_module("drivers", traffic["driver"]).run)
    limits = harness.load_json("cells", cell)["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    e2e, layer = harness.cell_metrics(BENCHMARK, cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert callable(harness.load_module("metrics", m).read)


def test_benchmark_json_names_the_files():
    """Each declared configuration is the file it names, with its source
    and cuts; each per-layer metric has a reader and moves a declared
    end-to-end metric of every workload it lists."""
    for c in BENCHMARK["configs"]:
        cfg = harness.load_json("configs", c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["source"] == cfg["source"]
        assert c["reduced"] == cfg["reduced"]
    for m in BENCHMARK["per_layer"]:
        harness.load_module("metrics", m["name"])
        for w in m.get("workloads", CELLS):
            assert m["moves"] in harness.cell_metrics(BENCHMARK, w)[0]


def _tiny(config):
    return TINY_TABLES[config] if config in TINY_TABLES else dict(
        harness.load_json("configs", config)["table"], scale=0.01)


@pytest.mark.parametrize("config", CONFIGS)
def test_tables_deterministic_in_seed(config):
    spec = _tiny(config)
    a = tables.make_table(spec, 2**31 + 3)
    b = tables.make_table(spec, 2**31 + 3)
    c = tables.make_table(spec, 2**31 + 4)
    assert a[0] == b[0] == c[0]
    assert np.array_equal(a[1], b[1])
    assert a[1].shape == c[1].shape and not np.array_equal(a[1], c[1])
    if a[2] is not None:
        assert np.array_equal(a[2], b[2])


@pytest.mark.parametrize("kw", [{}, {"scale": 0.25}])
def test_generator_copy_matches_program(kw):
    from repro.data import synthetic
    want = synthetic.bibsonomy_like(seed=0, **kw)
    sizes, tuples, values = tables.bibsonomy_like(seed=0, **kw)
    assert tuple(sizes) == want.sizes and values is None
    assert np.array_equal(tuples, want.tuples)


@pytest.fixture(scope="module")
def movielens():
    spec = harness.load_json("configs", "movielens1m-noac")["table"]
    return spec, {s: tables.make_table(spec, s) for s in (0, 2**31 + 5)}


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_ratings_one_star_per_pair(movielens, seed):
    """ml-1m's shape: every (user, movie) pair rated once, every user at
    least ``user_floor`` times, the published count of ratings."""
    spec, made = movielens
    sizes, t, v = made[seed]
    assert sizes == (spec["users"], spec["movies"], 5)
    assert t.shape == (sum(spec["star_counts"]), 3)
    pairs = t[:, 0].astype(np.int64) * sizes[1] + t[:, 1]
    assert np.unique(pairs).size == t.shape[0]
    per_user = np.bincount(t[:, 0], minlength=sizes[0])
    assert per_user.min() == spec["user_floor"]
    assert per_user.max() == spec["user_top"]
    assert np.count_nonzero(np.bincount(t[:, 1])) == spec["rated_movies"]
    assert np.array_equal(v, t[:, 2] + 1.0)


def test_ratings_same_work_every_seed(movielens):
    """Seeds differ in who rated what, not in how much: the same counts
    per user and per star."""
    spec, made = movielens
    (_, a, _), (_, b, _) = made.values()
    for col, n in ((0, spec["users"]), (2, 5)):
        ca = np.sort(np.bincount(a[:, col], minlength=n))
        cb = np.sort(np.bincount(b[:, col], minlength=n))
        assert np.array_equal(ca, cb)
    assert np.array_equal(np.bincount(a[:, 2]), spec["star_counts"])


def test_user_counts_by_hand():
    c = tables.user_counts(4, 20, 2, 9)
    assert c.sum() == 20 and c[0] == 9 and c[-1] == 2
    assert list(c) == sorted(c, reverse=True)
    with pytest.raises(ValueError):
        tables.user_counts(4, 100, 2, 9)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_without_tpu_fails_without_result():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], REPO)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "correct" not in p.stdout
    assert "no TPU" in p.stderr and "platform=cpu" in p.stderr


def test_run_with_only_the_benchmark_fails(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program
    to run: the run fails and prints no result."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_new_cell_file_is_listed_without_code_edit(tmp_path):
    """A cell added as data alone (its BENCHMARK.json entry and its
    limits file) is listed and resolved."""
    root = copy_bench(tmp_path)
    add_cell(root, CELLS[0], "throwaway.mine")
    p = _run(["--list"], tmp_path)
    assert p.returncode == 0
    assert "throwaway.mine" in p.stdout.split()
    job = harness.Job("throwaway.mine", 1, 1, False, 0.0, root=root)
    first = harness.Job(CELLS[0], 1, 1, False, 0.0, root=root)
    assert job.limits == first.limits and job.units == first.units
    assert set(job.readers) == set(first.readers)


def test_peaks_refuse_unknown_device():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.load_peaks("TPU v9 imaginary")
