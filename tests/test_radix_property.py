"""Properties of the radix sort backend (``core.radix``):

* the radix permutation is bit-identical to a stable ``lax.sort`` with
  an iota payload — including payload order among duplicate keys — for
  1- and 2-word keys, pruned pass plans, both device formulations
  (composite-word and Pallas histogram/rank kernels), and the host LSD
  argsort the streaming engine's chunk runs use,
* radix-backed mining equals the lax-backed *and* lexsort pipelines
  leaf-for-leaf (every ``PipelineResult`` field, permutations
  included), prime and NOAC, and the >64-bit lexsort fallback engages
  transparently,
* pass schedules prune to the plan's live bits (a 22-bit key never
  pays 64 bits of passes),
* the cardinality-pruned (rank-coded) value lane packs host≡device,
  orders exactly like the 32-bit float lane, and leaves every mining
  leaf bit-identical — δ-window queries included.

The seeded tests below always run; the hypothesis classes widen the
search in CI (the container has no hypothesis — same pattern as
``tests/test_keys_property.py``).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import BatchMiner, NOACMiner
from repro.core import keys as K
from repro.core import radix as RX


def _ref_sort(words, nw):
    t = words[0].shape[0]
    return jax.lax.sort(tuple(words) + (jnp.arange(t, dtype=jnp.int32),),
                        num_keys=nw, is_stable=True)


def _random_words(rng, t, live_bits, dup_frac=0.3):
    """Random packed key words with a controlled duplicate fraction
    (duplicates are what distinguishes a stable sort from any sort)."""
    n_distinct = max(1, int(t * (1.0 - dup_frac)))
    pool = rng.integers(0, 1 << min(live_bits, 63), n_distinct,
                        dtype=np.uint64)
    keys = pool[rng.integers(0, n_distinct, t)]
    if live_bits > 32:
        return (jnp.asarray((keys >> np.uint64(32)).astype(np.uint32)),
                jnp.asarray(keys.astype(np.uint32)))
    return (jnp.asarray(keys.astype(np.uint32)),)


@pytest.mark.parametrize("t", [1, 3, 257, 2000])
@pytest.mark.parametrize("live_bits", [1, 7, 15, 22, 28, 32, 33, 47, 60, 64])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_radix_perm_matches_stable_lax_sort(t, live_bits, use_pallas):
    rng = np.random.default_rng(t * 131 + live_bits)
    words = _random_words(rng, t, live_bits)
    ref = _ref_sort(words, len(words))
    perm = RX.radix_sort_perm(words, live_bits, use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref[-1]))
    s_words, (pay,) = K.sort_with_payload(
        words, (jnp.arange(t, dtype=jnp.int32),), backend="radix",
        live_bits=live_bits, use_pallas=use_pallas)
    for got, want in zip(s_words + (pay,), ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pass_schedule_prunes_to_live_bits():
    # 22 live bits at T<=32k: 17-bit digits -> 2 passes, never 64 bits' worth
    plan = RX.plan_radix(22, 30_000)
    assert plan.passes == 2
    assert sum(plan.widths) == 22
    assert plan.pos_bits == 15
    # the 8-bit histogram formulation of the issue's example: 3 passes
    assert RX.plan_radix(22, 30_000, digit_bits=8).passes == 3
    # degenerate and full-width cases
    assert RX.plan_radix(1, 4).passes == 1
    assert RX.plan_radix(64, 120_000).passes == 5   # 15-bit digits
    with pytest.raises(ValueError):
        RX.plan_radix(22, 30_000, digit_bits=32)


def test_resolve_sort_backend():
    assert RX.resolve_sort_backend(None, None, True) == "radix"
    assert RX.resolve_sort_backend("auto", True, True) == "radix"
    assert RX.resolve_sort_backend("lax", None, True) == "lax"
    assert RX.resolve_sort_backend(None, False, True) == "lexsort"
    assert RX.resolve_sort_backend("lexsort", True, True) == "lexsort"
    assert RX.resolve_sort_backend("radix", None, False) == "lexsort"
    with pytest.raises(ValueError):
        RX.resolve_sort_backend("quicksort", None, True)


def test_host_radix_argsort_matches_numpy():
    rng = np.random.default_rng(7)
    for t, live in [(1, 5), (500, 22), (4096, 60), (3000, 64)]:
        pool = rng.integers(0, 1 << min(live, 63), max(1, t // 2),
                            dtype=np.uint64)
        keys = pool[rng.integers(0, pool.shape[0], t)]
        np.testing.assert_array_equal(
            RX.radix_argsort_host(keys, live),
            np.argsort(keys, kind="stable"))


def _assert_results_identical(a, b):
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name)),
            err_msg=f.name)


def _random_ctx(rng, sizes, t, values):
    tuples = np.stack([rng.integers(0, s, t, dtype=np.int32)
                       for s in sizes], 1)
    vals = (rng.uniform(0.001, 1000.0, t).astype(np.float32)
            if values else None)
    return tuples, vals


@pytest.mark.parametrize("sizes", [(7, 5), (9, 7, 5), (6, 5, 4, 3)])
def test_radix_prime_mining_leaf_identical(sizes):
    rng = np.random.default_rng(len(sizes))
    tuples, _ = _random_ctx(rng, sizes, 120, values=False)
    engines = {b: BatchMiner(sizes, sort_backend=b)
               for b in ("radix", "lax", "lexsort")}
    assert engines["radix"].packed_active
    assert not engines["lexsort"].packed_active
    res = {b: e(tuples) for b, e in engines.items()}
    _assert_results_identical(res["radix"], res["lax"])
    _assert_results_identical(res["radix"], res["lexsort"])


@pytest.mark.parametrize("delta", [0.0, 50.0])
def test_radix_noac_mining_leaf_identical(delta):
    sizes = (9, 7, 5)
    rng = np.random.default_rng(int(delta) + 1)
    tuples, vals = _random_ctx(rng, sizes, 100, values=True)
    res = {b: NOACMiner(sizes, delta=delta, sort_backend=b)(tuples, vals)
           for b in ("radix", "lax", "lexsort")}
    _assert_results_identical(res["radix"], res["lax"])
    _assert_results_identical(res["radix"], res["lexsort"])


def test_radix_over_64_bit_key_falls_back_to_lexsort():
    sizes = (1 << 17,) * 4        # 68-bit key: no packed path
    rng = np.random.default_rng(0)
    tuples = np.stack([rng.integers(0, s, 64, dtype=np.int32)
                       for s in sizes], 1)
    auto = BatchMiner(sizes, sort_backend="radix")
    assert auto.resolved_sort_backend == "lexsort"
    _assert_results_identical(auto(tuples),
                              BatchMiner(sizes, packed=False)(tuples))


def test_streaming_host_radix_snapshot_identical():
    """The host-side LSD chunk sorts + merged permutations (radix
    backend) reproduce the device sort exactly: incremental snapshots
    equal a full re-mine leaf-for-leaf, and the lax-backed stream
    agrees bit-for-bit."""
    from repro.core import StreamingMiner
    sizes = (9, 7, 5)
    rng = np.random.default_rng(3)
    tuples, _ = _random_ctx(rng, sizes, 96, values=False)
    res = {}
    for b in ("radix", "lax"):
        sm = StreamingMiner(sizes, sort_backend=b)
        for lo in range(0, 96, 32):
            sm.add(tuples[lo:lo + 32])
        res[b] = sm.snapshot()
        _assert_results_identical(res[b], sm.snapshot(full_remine=True))
    _assert_results_identical(res["radix"], res["lax"])


# ---------------------------------------------------------------------------
# Value-lane cardinality pruning (rank-coded value lane)
# ---------------------------------------------------------------------------

def test_value_lane_pruning_plan_layout():
    sizes = (6000, 3000, 8)            # 13 + 12 + 3 = 28 structural bits
    full = K.plan_context_keys(sizes, with_values=True)[0]
    assert full.value_bits == 32 and full.total_bits == 60
    pruned = K.plan_context_keys(sizes, with_values=True, value_slots=5)[0]
    assert pruned.value_bits == 3      # 5-star movielens domain
    assert pruned.total_bits == 31 and pruned.words == 1
    assert pruned.seg_shift == pruned.e_bits + 3
    # pruning halves the radix pass schedule at movielens scale
    assert RX.plan_radix(pruned.total_bits, 64_055).passes == 2
    assert RX.plan_radix(full.total_bits, 64_055).passes == 4


@pytest.mark.parametrize("n_distinct", [1, 2, 5, 40, 1000])
def test_pruned_lane_pack_parity_and_order(n_distinct):
    """Host and device packers agree bit-for-bit on the rank lane, and
    the rank-coded key sorts in exactly the float-lane order (rank
    coding is order-isomorphic), stability included."""
    sizes = (9, 7, 5)
    rng = np.random.default_rng(n_distinct)
    tuples, _ = _random_ctx(rng, sizes, 300, values=False)
    domain = np.unique(rng.uniform(-50, 50, n_distinct).astype(np.float32))
    vals = domain[rng.integers(0, domain.shape[0], 300)]
    for k in range(len(sizes)):
        pruned = K.plan_mode_key(sizes, k, True, domain.shape[0])
        full = K.plan_mode_key(sizes, k, True)
        host = pruned.pack_host(tuples, vals, domain=domain)
        dev = pruned.pack_device(jnp.asarray(tuples), jnp.asarray(vals),
                                 domain=jnp.asarray(domain))
        packed = np.asarray(dev[-1], np.uint64)
        if pruned.words == 2:
            packed |= np.asarray(dev[0], np.uint64) << np.uint64(32)
        np.testing.assert_array_equal(host, packed)
        np.testing.assert_array_equal(
            np.argsort(host, kind="stable"),
            np.argsort(full.pack_host(tuples, vals), kind="stable"))
        # the lane round-trips through the domain gather
        vals_back = pruned.extract_values(dev, domain=jnp.asarray(domain))
        np.testing.assert_array_equal(np.asarray(vals_back), vals)


def test_pruning_rescues_float_lane_overflow():
    """A key that exceeds 64 bits ONLY because of the 32-bit float lane
    packs (and radix-sorts) once the lane is rank-coded: 41 structural
    bits + 32 > 64 un-pruned, but + 3 rank bits = 44 fits.  The pruned
    path must engage (domain not gated off by the un-pruned ``fits``)
    and stay leaf-identical to the lexsort fallback."""
    sizes = (1 << 14, 1 << 14, 1 << 13)          # 14 + 14 + 13 = 41 bits
    assert not K.plan_context_keys(sizes, with_values=True)[0].fits
    assert K.plan_context_keys(sizes, with_values=True,
                               value_slots=5)[0].fits
    rng = np.random.default_rng(9)
    tuples = np.stack([rng.integers(0, s, 80, dtype=np.int32)
                       for s in sizes], 1)
    vals = rng.integers(0, 5, 80).astype(np.float32)
    miner = NOACMiner(sizes, delta=1.0)
    assert miner.value_domain(vals) is not None   # pruning engages
    res = miner(tuples, vals)
    base = NOACMiner(sizes, delta=1.0, prune_values=False)(tuples, vals)
    _assert_results_identical(res, base)          # un-pruned = lexsort path


def test_negative_delta_rejected():
    """δ < 0 makes the window [v-δ, v+δ] empty and would underflow the
    rank-coded lane's searchsorted bounds — rejected at every entry."""
    from repro.core import pipeline as P
    with pytest.raises(ValueError, match="delta"):
        NOACMiner((4, 4, 4), delta=-0.5)
    with pytest.raises(ValueError, match="delta"):
        P.mine_tuples(jnp.zeros((4, 3), jnp.int32),
                      [jnp.zeros((4,), jnp.uint32)] * 3,
                      [jnp.zeros((4,), jnp.uint32)] * 3,
                      values=jnp.zeros((4,), jnp.float32), delta=-1.0)


@pytest.mark.parametrize("delta", [0.0, 7.5, 200.0])
def test_pruned_lane_mining_identical_to_float_lane(delta):
    """NOAC with the pruned (rank) lane ≡ the 32-bit float lane ≡ the
    column lexsort, leaf-for-leaf — δ-windows included (the rank-coded
    query bounds must match the sort-bit queries exactly)."""
    sizes = (9, 7, 5)
    rng = np.random.default_rng(int(delta) + 11)
    tuples, _ = _random_ctx(rng, sizes, 150, values=False)
    # a small domain with exact float values (δ arithmetic lands both
    # on and between domain points)
    vals = rng.integers(0, 8, 150).astype(np.float32) * np.float32(12.5)
    res = {}
    for name, kw in {"pruned": dict(sort_backend="radix"),
                     "float": dict(sort_backend="radix",
                                   prune_values=False),
                     "lax": dict(sort_backend="lax"),
                     "lexsort": dict(sort_backend="lexsort")}.items():
        res[name] = NOACMiner(sizes, delta=delta, **kw)(tuples, vals)
    _assert_results_identical(res["pruned"], res["float"])
    _assert_results_identical(res["pruned"], res["lax"])
    _assert_results_identical(res["pruned"], res["lexsort"])


# ---------------------------------------------------------------------------
# Hypothesis widening (CI only; mirrors tests/test_keys_property.py)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # pragma: no cover - CI installs it
    st = None

if st is not None:
    @st.composite
    def word_arrays(draw):
        t = draw(st.integers(1, 200))
        live = draw(st.integers(1, 64))
        seed = draw(st.integers(0, 2**16))
        dup = draw(st.floats(0.0, 0.9))
        rng = np.random.default_rng(seed)
        return _random_words(rng, t, live, dup), live

    @settings(max_examples=40, deadline=None)
    @given(word_arrays(), st.booleans())
    def test_hypothesis_radix_perm_stable(words_live, use_pallas):
        (words, live) = words_live
        ref = _ref_sort(words, len(words))
        perm = RX.radix_sort_perm(words, live, use_pallas=use_pallas)
        np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref[-1]))

    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 6), st.integers(2, 6), st.integers(2, 6),
           st.integers(1, 40), st.integers(0, 2**16),
           st.one_of(st.none(), st.floats(0.0, 500.0)))
    def test_hypothesis_radix_mining_leaf_identical(a, b, c, t, seed, delta):
        sizes = (a, b, c)
        rng = np.random.default_rng(seed)
        tuples, vals = _random_ctx(rng, sizes, t, values=delta is not None)
        if delta is None:
            res = {k: BatchMiner(sizes, sort_backend=k)(tuples)
                   for k in ("radix", "lax", "lexsort")}
        else:
            res = {k: NOACMiner(sizes, delta=delta,
                                sort_backend=k)(tuples, vals)
                   for k in ("radix", "lax", "lexsort")}
        _assert_results_identical(res["radix"], res["lax"])
        _assert_results_identical(res["radix"], res["lexsort"])
