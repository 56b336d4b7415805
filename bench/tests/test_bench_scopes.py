"""The reduction of a trace by the program's own names
(``benchlib/scopes.py``), its readers, and ``scoped.py``: checked by
hand on small synthetic traces and on tiny runs on the CPU."""
from __future__ import annotations

import importlib.util
import json
import time

import pytest

from bench_testutil import BENCH, copy_bench, tiny_cell, \
    _cache_config_restored

from benchlib import harness, scopes, trace

EXCERPT = BENCH / "data" / "trace_excerpt.json"
SCOPED_EXCERPT = BENCH / "data" / "trace_excerpt_scoped.json"
OLD_READERS = ("device_idle_share.mine", "gather_ms_per_mine.mine",
               "scatter_ms_per_mine.mine", "radix_roofline.mine",
               "segment_reduce_roofline.mine")
STAGE_READERS = {s: f"{s}_ms_per_mine.mine" for s in
                 ("stage1_sort", "stage2_components", "delta_search",
                  "stage3_dedup")}
HOST_READER = "host_prep_ms_per_mine.mine"


def _read(metric, summary, facts):
    return harness.load_module("metrics", metric).read(
        summary, facts, harness.load_peaks("TPU v5 lite"))


def test_protobuf_fields_by_hand():
    # field 1 varint 150, field 2 bytes "hi", field 3 fixed32
    msg = bytes([0x08, 0x96, 0x01, 0x12, 0x02]) + b"hi" + \
        bytes([0x1d, 1, 2, 3, 4])
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in scopes._fields(memoryview(msg))]
    assert got == [(1, 150), (2, b"hi"), (3, bytes([1, 2, 3, 4]))]


#: two mines of a synthetic program: 1+3 ms of sorts, 2 ms of plain
#: components and 4 ms of δ searches, 1 ms of mixing, 2 ms of Stage 3,
#: and 0.5 ms the stage scopes do not cover
SYNTHETIC = {
    "devices": {"0": [["%a", 0, 1e6], ["%b", 1e6, 3e6], ["%c", 4e6, 2e6],
                      ["%d", 6e6, 4e6], ["%e", 10e6, 1e6],
                      ["%f", 11e6, 2e6], ["%g", 13e6, 0.5e6]]},
    "scopes": {"0": ["jit(m)/stage1_sort/radix", "jit(m)/stage1_sort/gather",
                     "jit(m)/stage2_components/gather",
                     "jit(m)/stage2_components/delta_search/while/gather",
                     "jit(m)/stage2_mix/mul", "jit(m)/stage3_dedup/sort",
                     ""]},
    "host": [["bench.mine", 0, 20e6], ["repro.mine.copy_in", 1e6, 3e6],
             ["repro.mine.value_domain", 0, 1e6],
             ["repro.mine.dispatch", 4e6, 1e6], ["repro.mine.wait", 5e6, 9e6],
             ["repro.mine.copy_in", 20e6, 2e6], ["bench.gc", 22e6, 1e6]],
}


def test_stage_readers_by_hand():
    s = scopes.summarize(SYNTHETIC, 0.05)
    facts = {"mines": 2}
    want = {"stage1_sort": 2.0, "stage2_components": 3.0,
            "delta_search": 2.0, "stage3_dedup": 1.0}
    for scope, metric in STAGE_READERS.items():
        assert _read(metric, s, facts) == pytest.approx(want[scope])
    # copy-in 3 + 2 ms, value domain 1 ms, dispatch 1 ms over two mines
    assert _read(HOST_READER, s, facts) == pytest.approx(3.5)
    assert scopes.stage_seconds(s) == pytest.approx(
        {"stage1_sort": 4e-3, "stage2_components": 6e-3, "stage2_mix": 1e-3,
         "stage3_dedup": 2e-3, "": 0.5e-3})


def test_gaps_take_the_innermost_program_span():
    s = scopes.summarize(SYNTHETIC, 0.05)
    assert [g[0] for g in s["idle_gaps"]] == []       # no gap between ops
    tr = dict(SYNTHETIC, devices={"0": [["%a", 0, 1e6], ["%b", 6e6, 1e6]]},
              scopes={"0": ["", ""]})
    gaps = scopes.summarize(tr, 0.05)["idle_gaps"]
    # the gap 1-6 ms has its middle (3.5 ms) in copy-in, inside bench.mine
    assert gaps == [["repro.mine.copy_in", pytest.approx(5e-3)]]


@pytest.fixture(scope="module")
def excerpt():
    with open(EXCERPT) as f:
        return json.load(f)


@pytest.mark.parametrize("metric", sorted(STAGE_READERS.values())
                         + [HOST_READER])
def test_new_readers_return_nothing_without_names(excerpt, metric):
    """On a trace reduced without scopes or program spans (the harness's
    own summary), and on one where no name matches, they read None."""
    s = trace.summarize(excerpt, excerpt["window_s"])
    assert _read(metric, s, excerpt["facts"]) is None
    bare = scopes.summarize({"devices": {"0": [["%x", 0, 5]]},
                             "scopes": {"0": ["jit(f)/other"]},
                             "host": [["bench.mine", 0, 9]]}, 1.0)
    assert _read(metric, bare, {"mines": 1}) is None


@pytest.mark.parametrize("metric", OLD_READERS)
def test_old_readers_unmoved_by_the_added_keys(excerpt, metric):
    """A summary that also carries scopes and program spans reads the
    same on the recorded excerpt as the harness's own."""
    plain = trace.summarize(excerpt, excerpt["window_s"])
    named = scopes.summarize(
        dict(excerpt, scopes={d: [""] * len(ev) for d, ev in
                              excerpt["devices"].items()},
             host=excerpt["host"] + [["repro.mine.copy_in", 0, 1e6]]),
        excerpt["window_s"])
    want = _read(metric, plain, excerpt["facts"])
    assert want is not None
    assert _read(metric, named, excerpt["facts"]) == want


@pytest.mark.parametrize("cell", ["bibsonomy-prime.mine",
                                  "movielens1m-noac.mine"])
def test_recorded_scoped_trace(cell):
    """Two warm mines of each cell recorded on the chip: the four stage
    scopes hold at least 95% of the device's busy time, every idle gap
    longer than a millisecond is labelled by a program phase (the gap
    at the mine boundary, over 0.1 s traced, by ``repro.mine.wait``),
    and the readers read what the stages took."""
    with open(SCOPED_EXCERPT) as f:
        ex = json.load(f)["cells"][cell]
    s = scopes.summarize(ex, ex["window_s"])
    stages = scopes.stage_seconds(s)
    assert set(stages) - {""} <= set(scopes.STAGES)
    assert sum(v for k, v in stages.items() if k) >= 0.95 * s["busy_s"]
    gaps = [g for g in s["idle_gaps"] if g[1] > 1e-3]
    assert max(g[1] for g in gaps) > 0.1
    assert all(g[0].startswith("repro.mine.") for g in gaps)
    facts = ex["facts"]
    ms = {scope: _read(metric, s, facts)
          for scope, metric in STAGE_READERS.items()}
    per_mine = 1e3 * s["busy_s"] / facts["mines"]
    if cell.startswith("movielens"):
        # the δ-window searches are most of a NOAC mine's device time
        assert 0.5 * per_mine < ms["delta_search"] < ms["stage2_components"]
    else:
        assert ms.pop("delta_search") is None
        # the sorts of Stages 1 and 3 are most of a prime mine's
        assert ms["stage1_sort"] + ms["stage3_dedup"] > 0.6 * per_mine
    assert sum(ms[k] for k in ("stage1_sort", "stage2_components",
                               "stage3_dedup")) <= per_mine
    assert 0 < _read(HOST_READER, s, facts) < 0.1 * per_mine


def _scoped_module(root):
    spec = importlib.util.spec_from_file_location("bench_scoped",
                                                  root / "scoped.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", ["bibsonomy-prime.mine",
                                  "movielens1m-noac.mine"])
def test_scoped_run_on_the_cpu(tmp_path, cell):
    """``scoped.py``'s traced run of a tiny cell on the CPU: the
    operations of the CPU's XLA threads join their instructions'
    op_names, the program's phases and the collections are host spans,
    and every new reader reads."""
    import jax
    root = copy_bench(tmp_path)
    name = tiny_cell(root, cell)
    job = harness.Job(name, 2**31 + 11, 1, True, time.perf_counter(),
                      root=root)

    def hold():
        job.device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
        job.devices = jax.devices()[:1]
        return job.device
    job.hold_devices = hold
    path = tmp_path / "excerpt.json"
    with _cache_config_restored(root.parent / ".jax_cache"):
        res = _scoped_module(root).run(
            job, planes=("/host:CPU", "tf_XLA"), excerpt_path=str(path))
    assert res["correct"] and res["mines"] >= 2
    noac = cell.startswith("movielens")
    for scope, metric in STAGE_READERS.items():
        value = res["metrics"][metric]
        if scope == "delta_search" and not noac:
            assert value is None
        else:
            assert value > 0
    assert res["metrics"][HOST_READER] > 0
    assert set(res["stage_ms_per_mine"]) >= set(scopes.STAGES)
    spans = res["host_span_counts"]
    for phase in ("copy_in", "dispatch", "wait"):
        assert spans[f"repro.mine.{phase}"] == res["mines"]
    assert ("repro.mine.value_domain" in spans) == noac
    ex = json.loads(path.read_text())
    assert ex["facts"]["mines"] == 2
    assert len(ex["devices"]["0"]) == len(ex["scopes"]["0"]) > 0


def test_gc_spans(tmp_path):
    import gc
    import glob
    import jax
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with scopes.gc_spans():
            gc.collect()
    gc.collect()                       # outside: no span, no error
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = [e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events]
    assert names.count("bench.gc") >= 1
