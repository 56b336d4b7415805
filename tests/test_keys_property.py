"""Hypothesis properties of the packed-key subsystem (``core.keys``):

* packed-key Stage-1/Stage-3 mining is bit-identical to the lexsort
  oracle — every ``PipelineResult`` leaf, including the per-mode sort
  permutations — across random contexts of arity 2–4, with and without
  value columns,
* contexts whose key exceeds 64 bits transparently fall back to the
  lexsort path behind the same API,
* host and device packers produce the same uint64 word bit-for-bit (the
  invariant the streaming engine's merged permutations rest on),
* the order-preserving float32 encoding is a strictly monotone bijection.
"""
import dataclasses

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import BatchMiner, NOACMiner
from repro.core import keys as K
from repro.core.context import PolyadicContext


@st.composite
def contexts(draw, max_arity=4, max_size=7, max_tuples=40,
             with_values=False):
    arity = draw(st.integers(2, max_arity))
    sizes = tuple(draw(st.integers(2, max_size)) for _ in range(arity))
    n = draw(st.integers(1, max_tuples))
    rows = draw(st.lists(
        st.tuples(*[st.integers(0, s - 1) for s in sizes]),
        min_size=n, max_size=n))
    vals = None
    if with_values:
        # finite, no -0.0/NaN: the documented domain of the
        # order-preserving float encoding (DESIGN.md §3a)
        vals = np.asarray(draw(st.lists(
            st.floats(float(np.float32(0.001)), 1000.0, width=32),
            min_size=n, max_size=n)),
            np.float32)
    return PolyadicContext(sizes, np.asarray(rows, np.int32), vals)


def assert_results_identical(a, b):
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name)),
            err_msg=f.name)


@settings(max_examples=15, deadline=None)
@given(contexts())
def test_packed_prime_bit_identical_to_lexsort(ctx):
    packed = BatchMiner(ctx.sizes, packed=True)
    oracle = BatchMiner(ctx.sizes, packed=False)
    assert packed.packed_active
    assert_results_identical(packed(ctx.tuples), oracle(ctx.tuples))


@settings(max_examples=15, deadline=None)
@given(contexts(with_values=True), st.floats(0.0, 2000.0))
def test_packed_noac_bit_identical_to_lexsort(ctx, delta):
    packed = NOACMiner(ctx.sizes, delta=delta, packed=True)
    oracle = NOACMiner(ctx.sizes, delta=delta, packed=False)
    assert packed.packed_active
    assert_results_identical(packed(ctx.tuples, ctx.values),
                             oracle(ctx.tuples, ctx.values))


def test_over_64_bit_key_falls_back_to_lexsort():
    # 4 modes × 17 bits = 68 key bits: no packed path
    sizes = (1 << 17,) * 4
    rng = np.random.default_rng(0)
    tuples = np.stack([rng.integers(0, s, 64, dtype=np.int32)
                       for s in sizes], 1)
    auto = BatchMiner(sizes)                    # packed=None → auto
    assert not auto.key_plans[0].fits
    assert not auto.packed_active
    assert_results_identical(auto(tuples),
                             BatchMiner(sizes, packed=False)(tuples))
    # value lane pushes a fitting prime key over the edge: 3×11+32 = 65
    nsz = (2048, 2048, 2048)
    assert K.plan_context_keys(nsz, with_values=False)[0].fits
    nm = NOACMiner(nsz, delta=10.0)
    assert not nm.packed_active
    vals = rng.uniform(0, 100, 64).astype(np.float32)
    ntup = np.stack([rng.integers(0, s, 64, dtype=np.int32)
                     for s in nsz], 1)
    assert_results_identical(
        nm(ntup, vals), NOACMiner(nsz, delta=10.0, packed=False)(ntup, vals))


@settings(max_examples=25, deadline=None)
@given(contexts(with_values=True))
def test_host_device_packers_bit_identical(ctx):
    for with_values in (False, True):
        vals = ctx.values if with_values else None
        for plan in K.plan_context_keys(ctx.sizes, with_values=with_values):
            host = plan.pack_host(ctx.tuples, vals)
            words = [np.asarray(w).astype(np.uint64)
                     for w in plan.pack_device(ctx.tuples, vals)]
            dev = (words[0] << np.uint64(32)) | words[1] \
                if plan.words == 2 else words[0]
            np.testing.assert_array_equal(host, dev)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(-float(np.float32(1e30)), float(np.float32(1e30)),
                          width=32), min_size=2, max_size=50))
def test_float_sort_bits_monotone_bijection(vals):
    v = np.asarray(vals, np.float32)
    v = np.where(v == 0, np.float32(0.0), v)    # normalise -0.0
    enc = K.float_sort_bits_host(v)
    # strictly order-preserving
    order = np.argsort(v, kind="stable")
    assert (np.diff(enc[order].astype(np.int64)) >= 0).all()
    eq = v[:, None] == v[None, :]
    assert (eq == (enc[:, None] == enc[None, :])).all()
    # device encode matches host; decode inverts exactly
    import jax.numpy as jnp
    dev = np.asarray(K.float_sort_bits(jnp.asarray(v)))
    np.testing.assert_array_equal(enc, dev)
    back = np.asarray(K.float_from_sort_bits(jnp.asarray(enc)))
    np.testing.assert_array_equal(back, v)
