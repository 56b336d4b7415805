"""The shuffle cell's own parts on the CPU: the generator of
``benchlib/bigratings.py``, the reference and comparison of
``benchlib/bigreference.py`` (against ``benchlib/reference.py`` and
against planted faults), the collective and slot-fill readers, and a
whole run of a tiny shuffle cell."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench_testutil import add_cell, copy_bench, run_on_cpu

from benchlib import bigratings, bigreference, compare, harness, reference, \
    tables

CELL = "movielens25m-noac.shuffle4"
CONFIG = harness.load_json("configs", "movielens25m-noac")
LIMITS = harness.load_json("cells", CELL)["limits"]

#: a table of the configuration's shape small enough for the CPU
TINY = {"generator": "ratings_by_rejection", "users": 200, "movies": 90,
        "rated_movies": 80, "star_counts": [40, 80, 40, 160, 130, 490, 320,
                                            660, 220, 360],
        "user_floor": 5, "user_top": 70, "movie_alpha": 0.65,
        "star_step": 0.5}


@pytest.mark.parametrize("scale", [0.005, 0.01])
def test_generator_gives_the_configured_counts(scale):
    spec = dict(CONFIG["table"], scale=scale)
    users, stars = bigratings.scaled(scale, spec["users"],
                                     spec["star_counts"])
    rows = sum(stars)
    want_users = np.sort(tables.user_counts(users, rows, spec["user_floor"],
                                            spec["user_top"]))
    tabs = [tables.make_table(spec, seed) for seed in (3, 2**31 + 17)]
    for sizes, tuples, values in tabs:
        assert sizes == (users, spec["movies"], 10)
        assert tuples.shape == (rows, 3)
        got = np.bincount(tuples[:, 0], minlength=users)
        assert np.array_equal(np.sort(got[got > 0]), want_users)
        assert np.array_equal(np.bincount(tuples[:, 2], minlength=10),
                              stars)
        pairs = tuples[:, 0].astype(np.int64) << 20 | tuples[:, 1]
        assert np.unique(pairs).size == rows      # one star a pair
        assert np.array_equal(values, (tuples[:, 2] + 1) * 0.5)
        assert len(np.unique(tuples[:, 1])) <= spec["rated_movies"]
    assert not np.array_equal(tabs[0][1], tabs[1][1])


def test_rejection_and_gumbel_draw_one_law():
    """Each user's first k distinct draws of a weighted stream and a
    Gumbel top-k pick each movie equally often, as successive weighted
    draws without replacement do."""
    logw = -0.9 * np.log(np.arange(1, 9))
    p = np.exp(logw) / np.exp(logw).sum()
    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    counts = np.full(20000, 3)
    _, rm = bigratings._rejection(np.random.default_rng(1), counts, p, cdf, 3)
    _, gm = bigratings._gumbel(np.random.default_rng(2), counts[:4000], logw)
    a = np.bincount(rm, minlength=8) / counts.size
    b = np.bincount(gm, minlength=8) / 4000
    assert np.allclose(a, b, atol=0.03), (a, b)
    assert a[0] > a[-1] * 2


def _oracle_clusters(want):
    """Cluster ids of ``reference.mine``'s answer, from the exact
    entity sets of each tuple's windows."""
    n, t = want["sorted_e"].shape
    ids, out = {}, np.empty(t, np.int64)
    for i in range(t):
        key = tuple(frozenset(want["sorted_e"][k][want["range_lo"][k, i]:
                                                  want["range_hi"][k, i]]
                              .tolist()) for k in range(n))
        out[i] = ids.setdefault(key, len(ids))
    cards = np.array([[len(set(want["sorted_e"][k][want["range_lo"][k, i]:
                                                   want["range_hi"][k, i]]
                               .tolist())) for i in range(t)]
                      for k in range(n)])
    return out, cards


def _random_valued(seed, t=2500):
    """Values drawn apart from the ids, so δ-windows split segments."""
    rng = np.random.default_rng(seed)
    rows = np.stack([rng.integers(0, 30, t), rng.integers(0, 20, t),
                     rng.integers(0, 6, t)], 1).astype(np.int32)
    rows = np.unique(rows, axis=0)
    rows = rows[rng.permutation(len(rows))]
    return rows, rng.choice(np.arange(1, 11, dtype=np.float32) / 2,
                            len(rows))


@pytest.mark.parametrize("case", ["ratings", "valued", "prime"])
def test_reference_agrees_with_the_window_reference(case):
    params = {"delta": 1.0}
    if case == "ratings":
        _, tuples, values = tables.make_table(TINY, 7)
    elif case == "valued":
        tuples, values = _random_valued(8)
        params = {"delta": 0.5}
    else:
        # duplicated rows: the first occurrence of each row generates
        _, tuples, values = tables.bibsonomy_like(seed=9, scale=0.004)
        params = {"theta": 0.0}
    want = reference.mine_config(params, tuples, values)
    got = bigreference.mine_config(params, tuples, values)
    cluster, cards = _oracle_clusters(want)
    assert np.array_equal(got["keep"], want["keep"])
    assert np.array_equal(got["density"], want["density"])
    assert np.array_equal(got["cardinalities"], cards)
    assert bigreference.cluster_mismatch(
        got["cluster"].astype(np.uint64), cluster) == 0
    assert (len(np.unique(got["cluster"])) == len(np.unique(cluster))
            < len(tuples))


def test_colliding_set_hashes_are_split_exactly(monkeypatch):
    """With every entity weighing the same, sets of one size collide;
    the element-wise check still gives each distinct set its own id."""
    tuples, values = _random_valued(10, t=800)
    want = bigreference.mine(tuples, values, delta=0.5)
    monkeypatch.setattr(bigreference, "_weights",
                        lambda n: np.ones(n, np.uint64))
    got = bigreference.mine(tuples, values, delta=0.5)
    assert bigreference.cluster_mismatch(got["cluster"].astype(np.uint64),
                                         want["cluster"]) == 0
    assert np.array_equal(got["keep"], want["keep"])


def _sound():
    _, tuples, values = tables.make_table(TINY, 11)
    want = bigreference.mine_config(CONFIG["mine"], tuples, values)
    got = bigreference.as_result(want, want["density"].astype(np.float32))
    return got, want


def _judge(got, want, **extra):
    numbers = bigreference.numbers(got, want)
    numbers.update({"repeat_mismatch": 0, "overflow": 0}, **extra)
    return compare.judge(numbers, LIMITS)


def test_sound_answer_is_correct():
    got, want = _sound()
    ok, table = _judge(got, want)
    assert ok, table
    assert set(table) == set(LIMITS)


def _kept_twice(got, want):
    """Two kept tuples of different clusters."""
    i, j = np.flatnonzero(want["keep"])[:2]
    assert want["cluster"][i] != want["cluster"][j]
    return i, j


@pytest.mark.parametrize("fault,number", [
    ("keep_flipped", "keep_mismatch"),
    ("cardinality_wrong", "card_mismatch"),
    ("clusters_merged", "cluster_mismatch"),
    ("record_dropped", "overflow"),
    ("mines_differ", "repeat_mismatch"),
])
def test_planted_fault_is_not_correct(fault, number):
    got, want = _sound()
    got = {k: np.array(v, copy=True) for k, v in got.items()}
    i, j = _kept_twice(got, want)
    extra = {}
    if fault == "keep_flipped":
        got["keep"][i] = False
    elif fault == "cardinality_wrong":
        got["cardinalities"][1, i] += 1
    elif fault == "clusters_merged":
        on_j = want["cluster"] == want["cluster"][j]
        got["sig_lo"][on_j] = got["sig_lo"][i]
        got["sig_hi"][on_j] = got["sig_hi"][i]
    elif fault == "record_dropped":
        extra = {"overflow": 1}
    else:
        first = dict(got, density=got["density"].copy())
        first["density"][i] *= 2
        extra = {"repeat_mismatch": bigreference.repeat_mismatch(first, got)}
    ok, table = _judge(got, want, **extra)
    assert not ok
    assert table[number][0] > table[number][1]


def test_control_fails_on_density_alone():
    """The reference with its density in bfloat16, in the program's
    place (``control_shuffle.py``): not correct, and by
    ``density_rel_gap`` alone."""
    import control_shuffle
    for seed in (1, 2**31 + 5):
        numbers, _ = control_shuffle.control_numbers(
            dict(CONFIG, table=TINY), seed)
        ok, table = compare.judge(numbers, LIMITS)
        assert not ok
        assert [n for n, (v, lim) in table.items() if v > lim] \
            == ["density_rel_gap"]


def _summary(events, mines):
    return ({"devices": events, "host": []}, {"mines": mines})


#: two chips' operations of two mines, in the trace's HLO text
CHIP = [
    ["%all_to_all.3 = u32[2,8,2]{1,2,0} all-to-all(u32[2,8,2]{1,2,0} %p), "
     "replica_groups={{0,1}}, dimensions={0}", 0, 3e6],
    ["%all-to-all-start.1 = (u32[16,4]) all-to-all-start(u32[16,4] %r)",
     3e6, 1e6],
    ["%all-to-all-done.1 = u32[16,4]{1,0} all-to-all-done((u32[16,4]) "
     "%all-to-all-start.1)", 4e6, 2e6],
    ["%fusion.9 = u32[16]{0} fusion(u32[2,8,2]{1,2,0} %all_to_all.3), "
     "kind=kLoop", 6e6, 5e6],
    ["%all-gather-start.2 = (u32[8], u32[32]) all-gather-start(u32[8] "
     "%s), dimensions={0}", 11e6, 1e6],
    ["%all-gather-done.2 = u32[32]{0} all-gather-done((u32[8], u32[32]) "
     "%all-gather-start.2)", 12e6, 4e6],
    ["%all-reduce.7 = s32[256]{0} all-reduce(s32[256]{0} %h), "
     "to_apply=%add", 16e6, 2e6],
    ["%fusion.11 = u32[32]{0} fusion(u32[32]{0} %all-gather-done.2), "
     "kind=kLoop", 18e6, 7e6],
    ["%psum.26 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} %add.8), "
     "to_apply=%region", 25e6, 1e6],
    ["all-reduce-start.4", 26e6, 1e6],
]


def _read(metric, trace, facts):
    return harness.load_module("metrics", metric).read(
        trace, facts, harness.load_peaks("TPU v5 lite"))


def test_collective_readers_by_hand():
    second = [[n, s, 2 * d] for n, s, d in CHIP]
    trace, facts = _summary({"0": CHIP, "1": second}, 2)
    # all-to-all: (3 + 1 + 2) ms on chip 0, twice that on chip 1
    assert _read("all_to_all_ms_per_mine.shuffle4", trace, facts) \
        == pytest.approx((6 + 12) / 2 / 2)
    # all-gather pair and all-reduces: 9 ms on chip 0, 18 on chip 1
    assert _read("all_gather_ms_per_mine.shuffle4", trace, facts) \
        == pytest.approx((9 + 18) / 2 / 2)
    plain = [["%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop",
              0, 1e6]]
    for metric in ("all_to_all_ms_per_mine.shuffle4",
                   "all_gather_ms_per_mine.shuffle4"):
        assert _read(metric, *_summary({"0": plain}, 2)) is None
        assert _read(metric, *_summary({"0": CHIP}, 0)) is None


def test_slot_fill_reader_by_hand():
    trace = {"devices": {}, "host": []}
    metric = "shuffle_slot_fill.shuffle4"
    assert _read(metric, trace, {"shuffle_records": 300,
                                 "shuffle_slots": 600}) == 50.0
    # a program without the counters
    assert _read(metric, trace, {"mines": 3}) is None


def test_scoped_split_holds_the_shuffle_stages(monkeypatch):
    """``scoped_shuffle.py`` runs ``scoped.py`` with the sharded
    program's stage scopes among the stages."""
    import importlib.util
    from bench_testutil import BENCH
    from benchlib import scopes
    spec = importlib.util.spec_from_file_location(
        "bench_scoped_shuffle", BENCH / "scoped_shuffle.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    seen = []
    monkeypatch.setattr(scopes, "STAGES", scopes.STAGES)
    monkeypatch.setattr(mod.scoped, "main",
                        lambda argv: seen.append(scopes.STAGES) or 0)
    assert mod.main(["--workload", CELL]) == 0
    assert set(mod.SHUFFLE_STAGES) <= set(seen[0])
    assert scopes.stage_of("jit(f)/shmap/shuffle_owner/delta_search/x") \
        == "shuffle_owner"


@pytest.fixture(scope="module")
def tiny_shuffle(tmp_path_factory):
    """A copy of the benchmark with ``tiny.<cell>``: the shuffle cell
    over a tiny table of its configuration, on one chip."""
    root = copy_bench(tmp_path_factory.mktemp("checkout"))
    cfg = dict(CONFIG, table=TINY)
    (root / "configs" / "tiny.movielens25m-noac.json").write_text(
        json.dumps(cfg))
    name = add_cell(root, CELL, f"tiny.{CELL}", "tiny.movielens25m-noac")
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == name:
            w["chips"] = 1
    (root.parent / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, name


def test_sound_shuffle_run_is_correct(tiny_shuffle):
    root, name = tiny_shuffle
    res = run_on_cpu(root, name)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == set(LIMITS)
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "mine_tuples_per_s"}


def test_shuffle_fault_is_not_correct(tiny_shuffle, monkeypatch):
    """A mine that answers with one more record in a kept cluster's
    component: the exact numbers catch it."""
    import dataclasses
    from repro.core import distributed as D
    root, name = tiny_shuffle
    call = D.DistributedMiner.__call__

    def broken(self, tuples, values=None):
        res = call(self, tuples, values)
        i = int(np.flatnonzero(np.asarray(res.keep))[0])
        return dataclasses.replace(
            res, cardinalities=res.cardinalities.at[0, i].add(1))
    monkeypatch.setattr(D.DistributedMiner, "__call__", broken)
    res = run_on_cpu(root, name)
    assert res["correct"] is False
    assert res["checks"]["card_mismatch"][0] > 0
