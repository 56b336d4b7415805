"""Hypothesis property tests on the system's invariants (brief
deliverable c). The core M/R-algebra properties the paper relies on:

* idempotence under tuple duplication (at-least-once delivery, §5.1 K3),
* invariance under tuple permutation (shard order never matters),
* Alg.-7 density bounds and exact cluster-count semantics vs the oracle.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import BatchMiner
from repro.core.reference import multimodal_clusters
from repro.core.context import PolyadicContext


@st.composite
def contexts(draw, max_arity=4, max_size=7, max_tuples=40):
    arity = draw(st.integers(2, max_arity))
    sizes = tuple(draw(st.integers(2, max_size)) for _ in range(arity))
    n = draw(st.integers(1, max_tuples))
    rows = draw(st.lists(
        st.tuples(*[st.integers(0, s - 1) for s in sizes]),
        min_size=n, max_size=n))
    return PolyadicContext(sizes, np.asarray(rows, np.int32))


@settings(max_examples=25, deadline=None)
@given(contexts(), st.randoms(use_true_random=False))
def test_duplication_and_permutation_invariance(ctx, rnd):
    """mine(I) == mine(shuffle(I + duplicates)) on cluster signatures —
    the paper's M/R at-least-once argument (§5.1) as an algebra law."""
    miner = BatchMiner(ctx.sizes)
    base = miner(ctx.tuples)

    idx = list(range(ctx.num_tuples)) + [
        rnd.randrange(ctx.num_tuples) for _ in range(ctx.num_tuples // 2)]
    rnd.shuffle(idx)
    noisy = miner(ctx.tuples[np.asarray(idx)])

    def cluster_set(res):
        u = np.asarray(res.is_unique)
        return set(zip(np.asarray(res.sig_lo)[u].tolist(),
                       np.asarray(res.sig_hi)[u].tolist(),
                       np.asarray(res.gen_count)[u].tolist(),
                       np.asarray(res.volume)[u].tolist()))

    assert cluster_set(base) == cluster_set(noisy)


@settings(max_examples=25, deadline=None)
@given(contexts())
def test_matches_oracle_and_density_bounds(ctx):
    miner = BatchMiner(ctx.sizes)
    res = miner(ctx.tuples)
    _, unique, density, _ = multimodal_clusters(ctx)
    assert int(np.asarray(res.is_unique).sum()) == len(unique)
    d = np.asarray(res.density)
    vol = np.asarray(res.volume)
    gen = np.asarray(res.gen_count)
    assert (d > 0).all() and (d <= 1.0 + 1e-6).all()
    np.testing.assert_allclose(d, gen / np.maximum(vol, 1.0), rtol=1e-6)
    # every generating tuple's cluster contains the tuple itself =>
    # gen_count >= 1 and volume >= 1
    assert (gen >= 1).all() and (vol >= 1).all()


@settings(max_examples=15, deadline=None)
@given(contexts(max_arity=3, max_size=6, max_tuples=24),
       st.floats(0.05, 1.0))
def test_theta_filter_monotone(ctx, theta):
    """Raising θ never yields more kept clusters; θ=0 keeps all unique."""
    m0 = BatchMiner(ctx.sizes, theta=0.0)
    mt = BatchMiner(ctx.sizes, theta=theta)
    r0, rt = m0(ctx.tuples), mt(ctx.tuples)
    k0 = int(np.asarray(r0.keep).sum())
    kt = int(np.asarray(rt.keep).sum())
    assert kt <= k0
    assert k0 == int(np.asarray(r0.is_unique).sum())
