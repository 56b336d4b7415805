"""NOAC's δ-window bounds (``pipeline._delta_bounds``) by its two
formulations over one rank-coded ``SortedMode``: rank-threshold segment
scans and two binary searches over the sorted key words give the same
[lo, hi) windows, bit for bit; ``delta_bounds_path`` picks between them
by the sweep-count rule, and a miner with a hub counts the path of every
mode of every NOAC mine."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BatchMiner, NOACMiner, PolyadicContext
from repro.core import keys as K
from repro.core import pipeline as P
from repro.core import reference as ref
from repro.obs import Obs

#: rows of the property tables; ``EDGE`` is the largest domain the
#: scans take at this size (D − 1 == one search's steps)
ROWS = 100
EDGE = K.search_steps(ROWS) + 1

#: entity sizes per plan shape: a one-word key; a two-word key; and two
#: words with mode 1's rank lane across the word boundary (e_1 takes 30
#: bits, the 2-4 bit lane sits at offsets 30 and up)
PLANS = {"one_word": (7, 9, 6),
         "two_words": (1 << 12, 1 << 12, 1 << 8),
         "lane_across_words": (3, 1 << 30)}


def _table(sizes, domain, seed):
    """ROWS rows: half drawn from 2-3 entities a mode (long key segments
    of mixed values), half spread over the whole sizes (mostly one-row
    segments); values from ``domain``, each zero as -0.0 or +0.0."""
    rng = np.random.default_rng(seed)
    half = ROWS // 2
    dense = [rng.integers(0, min(s, 2 + j % 2), half)
             for j, s in enumerate(sizes)]
    spread = [rng.integers(0, s, ROWS - half) for s in sizes]
    tuples = np.stack([np.concatenate(c) for c in zip(dense, spread)],
                      1).astype(np.int32)
    values = domain[rng.integers(0, domain.size, ROWS)]
    zero = values == 0
    values[zero] = np.where(rng.random(int(zero.sum())) < 0.5,
                            np.float32(-0.0), np.float32(0.0))
    return tuples, values


def _domain(d, seed):
    """``d`` distinct quarter-step values around 0, 0 among them: a
    value ± 0.5 or ± 1 often lands exactly on another (inclusive
    window edges)."""
    rng = np.random.default_rng(seed)
    grid = np.arange(-12, 13, dtype=np.float32) / 4
    rest = rng.choice(grid[grid != 0], d - 1, replace=False)
    return np.sort(np.concatenate([[0.0], rest])).astype(np.float32)


def _both_paths(monkeypatch, sm, values, delta, dom):
    out = {}
    for path in ("runs", "search"):
        monkeypatch.setattr(P, "delta_bounds_path", lambda t, v, p=path: p)
        out[path] = [np.asarray(b) for b in
                     P._delta_bounds(sm, values, delta, dom)]
    return out


@pytest.mark.parametrize("delta", [0.0, 0.5, 1.0, 1e6])
@pytest.mark.parametrize("slots", [1, 2, 5, 10, EDGE])
def test_rank_threshold_scans_bit_identical_to_the_search(monkeypatch,
                                                          slots, delta):
    domain = _domain(slots, seed=slots)
    for i, (shape, sizes) in enumerate(PLANS.items()):
        tuples, values = _table(sizes, domain, seed=17 * slots + i)
        dom = jnp.asarray(K.value_domain_host(values))
        plans = K.plan_context_keys(sizes, True, int(dom.shape[0]))
        assert plans[0].words == (1 if shape == "one_word" else 2)
        assert plans[0].value_bits < 32
        tj, vj = jnp.asarray(tuples), jnp.asarray(values)
        for k, plan in enumerate(plans):
            sm = P.sort_mode(tj, k, values=vj, plan=plan, value_domain=dom)
            got = _both_paths(monkeypatch, sm, vj, delta, dom)
            for b, name in enumerate(("lo", "hi")):
                np.testing.assert_array_equal(
                    got["runs"][b], got["search"][b],
                    err_msg=f"{shape} mode {k} {name}")


def _ratings(users=60, movies=40, stars=5, per_user=(2, 14), seed=3):
    """An ml-1m-shaped table: users × movies × stars, one star per
    (user, movie) pair, the star both the third mode and the value."""
    rng = np.random.default_rng(seed)
    rows = []
    for u in range(users):
        for m in rng.choice(movies, rng.integers(*per_user), replace=False):
            s = rng.integers(0, stars)
            rows.append((u, m, s))
    tuples = np.asarray(rows, np.int32)
    return (users, movies, stars), tuples, (tuples[:, 2] + 1).astype(
        np.float32)


def test_ml1m_shaped_noac_mine_matches_lexsort_and_reference():
    sizes, tuples, values = _ratings()
    assert P.delta_bounds_path(len(tuples), 5) == "runs"
    runs = NOACMiner(sizes, delta=1.0)
    runs.obs = Obs.create()
    got = runs(tuples, values)
    want = NOACMiner(sizes, delta=1.0, packed=False)(tuples, values)
    for f in ("sig_lo", "sig_hi", "keep", "density", "cardinalities",
              "range_lo", "range_hi", "sorted_e", "perms"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f)
    assert _paths(runs.obs) == {"runs": len(sizes)}
    ctx = PolyadicContext(sizes, tuples, values)
    mined = sorted(tuple(sorted(map(sorted, c)))
                   for c, _ in runs.materialise(got))
    oracle = sorted(tuple(sorted(map(sorted, c)))
                    for c in ref.noac(ctx, 1.0))
    assert mined == oracle


def _paths(obs) -> dict:
    doc = obs.metrics.to_dict().get("pipeline_delta_bounds_total",
                                    {"series": []})
    return {r["labels"]["path"]: r["value"] for r in doc["series"]}


def test_delta_bounds_path_rule():
    t = 1_000_209
    assert K.search_steps(t) == 21
    assert P.delta_bounds_path(t, 5) == "runs"
    assert P.delta_bounds_path(t, 22) == "runs"        # 21 thresholds
    assert P.delta_bounds_path(t, 23) == "search"
    assert P.delta_bounds_path(t, None) == "search"     # float lane
    assert P.delta_bounds_path(ROWS, EDGE) == "runs"
    assert P.delta_bounds_path(ROWS, EDGE + 1) == "search"
    assert P.delta_bounds_path(1, 1) == "runs"


def test_delta_bounds_counter_per_mode_per_mine():
    sizes, tuples, values = _ratings(users=20, movies=15)
    runs = NOACMiner(sizes, delta=1.0)
    runs.obs = Obs.create()
    runs(tuples, values)
    runs(tuples, values)
    assert _paths(runs.obs) == {"runs": 6}
    # no rank-coded lane: the float lane (pruning off), the lexsort path
    for kw in ({"prune_values": False}, {"packed": False}):
        m = NOACMiner(sizes, delta=1.0, **kw)
        m.obs = Obs.create()
        m(tuples, values)
        assert _paths(m.obs) == {"search": 3}, kw
    # more domain values than one search has steps
    wide = np.arange(len(tuples), dtype=np.float32)
    assert P.delta_bounds_path(len(tuples), len(tuples)) == "search"
    m = NOACMiner(sizes, delta=1.0)
    m.obs = Obs.create()
    m(tuples, wide)
    assert _paths(m.obs) == {"search": 3}
    prime = BatchMiner(sizes)
    prime.obs = Obs.create()
    prime(tuples)
    assert _paths(prime.obs) == {}
