"""The comparison that decides ``correct``: the reference agrees with
the paper-literal oracle, a sound run passes, and the control and each
fault a mine cell can have come out as not correct."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench_testutil import (TINY_TABLES, add_cell, copy_bench,
                            run_on_cpu, tiny_cell)

from benchlib import compare, harness, reference, tables

SPECS = {w["name"]: w for w in harness.load_benchmark()["workloads"]}
MINE_CELLS = sorted(
    c for c, w in SPECS.items()
    if harness.load_json("traffic", w["traffic"])["driver"] == "mine_loop")


def _tiny_config(cell):
    cfg = harness.load_json("configs", SPECS[cell]["config"])
    cfg["table"] = TINY_TABLES[cfg["name"]]
    return cfg


@pytest.mark.parametrize("cell", MINE_CELLS)
def test_reference_matches_paper_oracle(cell):
    from repro.core import PolyadicContext
    from repro.core import reference as R
    cfg = _tiny_config(cell)
    sizes, tuples, values = tables.make_table(cfg["table"], 5)
    want = reference.mine_config(cfg["mine"], tuples, values)
    ctx = PolyadicContext(sizes, tuples, values)
    if values is None:
        _, _, dens, kept = R.multimodal_clusters(ctx)
    else:
        kept = R.noac(ctx, cfg["mine"]["delta"])
    oracle = {tuple(tuple(sorted(c)) for c in cl) for cl in kept}
    got = set()
    for i in np.flatnonzero(want["keep"]):
        got.add(tuple(
            tuple(sorted(set(want["sorted_e"][k][want["range_lo"][k, i]:
                                                 want["range_hi"][k, i]]
                             .tolist())))
            for k in range(len(sizes))))
    assert got == oracle
    if values is None:
        for i in np.flatnonzero(want["keep"]):
            key = tuple(tuple(sorted(set(
                want["sorted_e"][k][want["range_lo"][k, i]:
                                    want["range_hi"][k, i]].tolist())))
                for k in range(len(sizes)))
            assert want["density"][i] == pytest.approx(dens[key], rel=1e-12)


@pytest.mark.parametrize("cell", MINE_CELLS)
def test_control_is_not_correct(cell):
    import control
    cfg = _tiny_config(cell)
    limits = harness.load_json("cells", cell)["limits"]
    for seed in (1, 2, 2**31 + 5):
        numbers = control.control_numbers(cfg, seed)
        ok, _ = compare.judge(numbers, limits)
        assert not ok
        assert numbers["density_rel_gap"] > limits["density_rel_gap"]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = copy_bench(tmp_path_factory.mktemp("checkout"))
    return root, {c: tiny_cell(root, c) for c in MINE_CELLS}


@pytest.mark.parametrize("cell", MINE_CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    root, names = tiny_root
    res = run_on_cpu(root, names[cell])
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "mine_tuples_per_s"}


def test_traffic_options_reach_the_program(tiny_root, monkeypatch):
    """A traffic file's ``options`` go to ``mine`` and the run stays
    correct: a windowed mix is a data file alone."""
    import json
    import repro.core
    root, names = tiny_root
    name = add_cell(root, names[MINE_CELLS[0]], "tiny.windowed")
    traffic = {"driver": "mine_loop", "backend": "batch",
               "options": {"window_budget": 1024}}
    (root / "traffic" / "windowed.json").write_text(json.dumps(traffic))
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == name:
            w["traffic"] = "windowed"
    (root.parent / "BENCHMARK.json").write_text(json.dumps(bench))
    seen = []
    mine = repro.core.mine

    def spy(ctx, **kw):
        seen.append(kw)
        return mine(ctx, **kw)
    monkeypatch.setattr(repro.core, "mine", spy)
    res = run_on_cpu(root, name)
    assert res["correct"] is True, res["checks"]
    assert seen and seen[0]["window_budget"] == 1024


def _altered(result):
    """One answer altered where it is produced: a kept cluster's first
    component window grows by one row."""
    import jax.numpy as jnp
    i = int(np.flatnonzero(np.asarray(result.keep))[0])
    return dataclasses.replace(
        result, range_hi=result.range_hi.at[0, i].add(jnp.int32(1)))


def _half(miner, call, tuples, values=None):
    """Half of the table left out: its second half replaced by copies of
    the first, the shapes kept."""
    t = np.asarray(tuples)
    h = t.shape[0] // 2
    t = np.concatenate([t[:h], t[:t.shape[0] - h]])
    if values is not None:
        v = np.asarray(values)
        values = np.concatenate([v[:h], v[:v.shape[0] - h]])
    return call(miner, t, values)


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
@pytest.mark.parametrize("cell", MINE_CELLS)
def test_fault_is_not_correct(tiny_root, cell, fault, monkeypatch):
    from repro.core import pipeline as P
    root, names = tiny_root
    call = P.PipelineMiner.__call__
    if fault == "answer_altered":
        def broken(self, tuples, values=None):
            return _altered(call(self, tuples, values))
    else:
        def broken(self, tuples, values=None):
            return _half(self, call, tuples, values)
    monkeypatch.setattr(P.PipelineMiner, "__call__", broken)
    res = run_on_cpu(root, names[cell])
    assert res["correct"] is False
    exact = ("sorted_e_mismatch", "range_mismatch", "keep_mismatch")
    assert any(res["checks"][n][0] > 0 for n in exact)
