"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# signature
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,e", [(8, 128), (16, 512), (256, 1024), (3, 77)])
def test_signature(t, e):
    rng = np.random.default_rng(5)
    mask = jnp.asarray(rng.integers(0, 2, (t, e)), jnp.uint32)
    r = jnp.asarray(rng.integers(1, 2**32, e, dtype=np.uint32))
    out = ops.set_signature(mask, r)
    want = ref.signature_ref(mask, r)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_signature_order_independent():
    rng = np.random.default_rng(6)
    e = 128
    r = jnp.asarray(rng.integers(1, 2**32, e, dtype=np.uint32))
    m1 = np.zeros((8, e), np.uint32)
    m1[:, rng.choice(e, 20, replace=False)] = 1
    s1 = ops.set_signature(jnp.asarray(m1), r)
    assert len(set(np.asarray(s1).tolist())) == 1  # identical sets hash equal


# ---------------------------------------------------------------------------
# tricluster density
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g,m,b,t", [(8, 16, 16, 8), (16, 8, 32, 128),
                                     (7, 5, 9, 3)])
def test_tricluster_density(g, m, b, t):
    rng = np.random.default_rng(7)
    tensor = jnp.asarray(rng.integers(0, 2, (g, m, b)), jnp.float32)
    x = jnp.asarray(rng.integers(0, 2, (t, g)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, (t, m)), jnp.float32)
    z = jnp.asarray(rng.integers(0, 2, (t, b)), jnp.float32)
    out = ops.tricluster_density(tensor, x, y, z)
    want = ref.tricluster_density_ref(tensor, x, y, z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_exact_density_against_brute_force():
    """Kernel numerator equals a literal triple-loop box count."""
    rng = np.random.default_rng(8)
    g, m, b, t = 6, 7, 8, 4
    tensor = rng.integers(0, 2, (g, m, b))
    x = rng.integers(0, 2, (t, g))
    y = rng.integers(0, 2, (t, m))
    z = rng.integers(0, 2, (t, b))
    want = np.zeros(t)
    for ti in range(t):
        for gi in range(g):
            for mi in range(m):
                for bi in range(b):
                    want[ti] += (x[ti, gi] * y[ti, mi] * z[ti, bi]
                                 * tensor[gi, mi, bi])
    out = ops.tricluster_density(jnp.asarray(tensor, jnp.float32),
                                 jnp.asarray(x, jnp.float32),
                                 jnp.asarray(y, jnp.float32),
                                 jnp.asarray(z, jnp.float32))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


# ---------------------------------------------------------------------------
# fused segment reduce (masked prefix sums)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,bt", [(8, 8), (40, 16), (100, 32), (1024, 256),
                                  (5000, 1024),
                                  # one row, one tile less one, ragged
                                  # blocks of one and of several tiles,
                                  # a grid of the default 64-tile blocks
                                  (1, 1024), (1023, 1024), (3001, 1024),
                                  (9000, 4096), (70000, 64 * 1024)])
def test_segment_reduce(t, bt):
    rng = np.random.default_rng(9)
    w_lo = jnp.asarray(rng.integers(0, 2**32, t, dtype=np.uint32))
    w_hi = jnp.asarray(rng.integers(0, 2**32, t, dtype=np.uint32))
    first = jnp.asarray(rng.random(t) < 0.6)
    got = ops.segment_reduce(w_lo, w_hi, first, bt=bt)
    want = ref.segment_reduce_ref(w_lo, w_hi, first)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_segment_reduce_uint32_wraparound():
    """Prefix sums must wrap mod 2^32 exactly (range differences of the
    mining signatures rely on modular arithmetic)."""
    w = jnp.full((64,), 0xFFFFFFFF, jnp.uint32)
    f = jnp.ones((64,), bool)
    lo, hi, cnt = ops.segment_reduce(w, w, f, bt=16)
    want = np.cumsum(np.full(64, 0xFFFFFFFF, np.uint64)).astype(np.uint32)
    np.testing.assert_array_equal(np.asarray(lo), want)
    np.testing.assert_array_equal(np.asarray(cnt), np.arange(1, 65))


@pytest.mark.parametrize("t,bt", [(1, 1024), (1000, 1024), (2049, 1024),
                                  (9000, 2048)])
def test_segment_reduce_wraparound_blocks(t, bt):
    """Wrap-around mod 2^32 inside a block, across the row ladder and
    across the grid carries, under a random first-occurrence mask."""
    rng = np.random.default_rng(t)
    w = np.full(t, 0xFFFFFFFF, np.uint32)
    w[::7] = 0x80000001
    f = rng.random(t) < 0.7
    lo, hi, cnt = ops.segment_reduce(jnp.asarray(w), jnp.asarray(w[::-1]),
                                     jnp.asarray(f), bt=bt)
    for got, src in ((lo, w), (hi, w[::-1])):
        want = np.cumsum(np.where(f, src, 0).astype(np.uint64)) \
            % (1 << 32)
        np.testing.assert_array_equal(np.asarray(got),
                                      want.astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(cnt), np.cumsum(f))


@pytest.mark.parametrize("t,bt", [(1, 1024), (1024, 1024), (1025, 1024),
                                  (5000, 2048), (100, 1 << 20)])
def test_block_len(t, bt):
    """Blocks are whole (8, 128) tiles, at most ``bt`` rounded up to a
    tile, and never more tiles than the stream needs."""
    blk = ops._block_len(t, bt)
    assert blk % ops.TILE == 0 and blk >= ops.TILE
    assert blk < bt + ops.TILE
    assert blk < t + ops.TILE


def test_interpret_decided_by_backend(monkeypatch):
    """Off a TPU kernels are interpreted unless a caller compiles for a
    described chip; on a TPU they never are."""
    assert ops._interpret(None) is True
    assert ops._interpret(False) is False
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops._interpret(None) is False
    with pytest.raises(ValueError):
        ops._interpret(True)


@pytest.mark.parametrize("module", ["repro.kernels.radix_sort",
                                    "repro.kernels.ref", "repro.core.radix"])
def test_radix_modules_import_first(module):
    """Each radix module imports first in a fresh interpreter: the
    kernels import nothing from ``core``, so there is no cycle."""
    import subprocess
    import sys
    proc = subprocess.run([sys.executable, "-c", f"import {module}"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_segment_reduce_in_pipeline():
    """The fused kernel (interpret mode on CPU) is bit-identical to the
    jnp oracle through the full mining pipeline, both variants."""
    from repro.core import BatchMiner, NOACMiner
    from repro.data import synthetic
    ctx = synthetic.random_context((7, 6, 5), 64, seed=3)
    a = BatchMiner(ctx.sizes, use_pallas=True)(ctx.tuples)
    b = BatchMiner(ctx.sizes, use_pallas=False)(ctx.tuples)
    np.testing.assert_array_equal(np.asarray(a.sig_lo), np.asarray(b.sig_lo))
    np.testing.assert_array_equal(np.asarray(a.gen_count),
                                  np.asarray(b.gen_count))
    ctxv = synthetic.random_context((7, 6, 5), 64, seed=4,
                                    values=True).deduplicated()
    av = NOACMiner(ctxv.sizes, delta=60.0, use_pallas=True)(
        ctxv.tuples, ctxv.values)
    bv = NOACMiner(ctxv.sizes, delta=60.0, use_pallas=False)(
        ctxv.tuples, ctxv.values)
    np.testing.assert_array_equal(np.asarray(av.sig_lo),
                                  np.asarray(bv.sig_lo))
    np.testing.assert_array_equal(np.asarray(av.density),
                                  np.asarray(bv.density))


# ---------------------------------------------------------------------------
# radix sort primitives (one-sweep histograms + per-pass stable ranks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,bt,live", [(8, 8, 5), (100, 32, 22),
                                       (513, 128, 28), (1024, 256, 60),
                                       (2000, 512, 64),
                                       # T of one, one exact tile, and a
                                       # ragged grid of tiles
                                       (1, 1024, 3), (1024, 1024, 40),
                                       (3001, 1024, 64)])
def test_radix_histogram(t, bt, live):
    from repro.core.radix import plan_radix
    rng = np.random.default_rng(t)
    keys = rng.integers(0, 1 << min(live, 63), t, dtype=np.uint64)
    words = ([jnp.asarray((keys >> np.uint64(32)).astype(np.uint32)),
              jnp.asarray(keys.astype(np.uint32))] if live > 32
             else [jnp.asarray(keys.astype(np.uint32))])
    plan = plan_radix(live, t, digit_bits=8)
    got = ops.radix_histogram(words, plan.shifts, plan.widths, bt=bt)
    want = ref.radix_histogram_ref(words, plan.shifts, plan.widths)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(got).sum()) == t * plan.passes


@pytest.mark.parametrize("t,bt", [(8, 8), (100, 32), (513, 128),
                                  (2000, 512), (1, 1024), (1024, 1024),
                                  (3001, 1024)])
def test_radix_rank(t, bt):
    rng = np.random.default_rng(t + 1)
    dig = rng.integers(0, 256, t).astype(np.uint32)
    hist = np.bincount(dig, minlength=256)
    starts = jnp.asarray(np.concatenate([[0], np.cumsum(hist)[:-1]])
                         .astype(np.int32))
    digits = jnp.asarray(dig)
    got = ops.radix_rank(digits, starts, bt=bt)
    want = ref.radix_rank_ref(digits, starts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ranks are the stable counting-sort permutation: bijective and
    # digit-ordered, ties in input order
    r = np.asarray(got)
    assert sorted(r.tolist()) == list(range(t))
    assert (dig[np.argsort(r)] == np.sort(dig, kind="stable")).all()
