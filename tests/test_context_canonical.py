"""Valued-context canonicalisation (core/context.py): V must be a
function of the tuple (paper §3.2), so duplicate rows of a many-valued
context collapse at construction with the *last* value winning — the
upsert semantics of the online algorithm.

Regression for the historical ``benchmarks/table5.py`` NOAC(100,0.5,0)
seq-vs-par MISMATCH: the frames-like dataset carries duplicate triples
with conflicting frequencies, and before canonicalisation the
sequential reference and the vectorised engine resolved the conflict
differently.
"""
import dataclasses

import numpy as np

from repro.core import NOACMiner
from repro.core import reference as R
from repro.core.context import PolyadicContext
from repro.data import synthetic


def test_valued_duplicates_keep_last():
    rows = np.array([[0, 1, 2], [1, 0, 0], [0, 1, 2], [0, 1, 2]], np.int32)
    vals = np.array([1.0, 5.0, 2.0, 3.0], np.float32)
    ctx = PolyadicContext((2, 2, 3), rows, vals)
    assert ctx.num_tuples == 2
    got = {tuple(r): v for r, v in zip(ctx.tuples.tolist(),
                                       ctx.values.tolist())}
    assert got == {(0, 1, 2): 3.0, (1, 0, 0): 5.0}


def test_unvalued_duplicates_stay_legal():
    rows = np.array([[0, 1], [0, 1], [1, 0]], np.int32)
    ctx = PolyadicContext((2, 2), rows)
    assert ctx.num_tuples == 3          # M/R at-least-once: dups legal


def test_consistent_duplicates_also_collapse():
    rows = np.array([[0, 0], [0, 0]], np.int32)
    ctx = PolyadicContext((1, 1), rows, np.array([7.0, 7.0], np.float32))
    assert ctx.num_tuples == 1
    assert float(ctx.values[0]) == 7.0


def test_empty_valued_context_ok():
    ctx = PolyadicContext((2, 2), np.zeros((0, 2), np.int32),
                          np.zeros((0,), np.float32))
    assert ctx.num_tuples == 0


def test_table5_noac_seq_vs_par_parity():
    """The exact table5 configuration that used to MISMATCH:
    NOAC(100, 0.5, 0) on a frames-like slice with conflicting-value
    duplicate triples."""
    full = synthetic.semantic_frames_like(n_tuples=800, seed=0)
    # construction already canonicalised; re-introduce the benchmark's
    # slicing pattern to mirror table5.run exactly
    sub = dataclasses.replace(full, tuples=full.tuples[:400],
                              values=full.values[:400])
    seq = R.noac(sub, 100.0, rho_min=0.5, minsup=0)
    miner = NOACMiner(full.sizes, delta=100.0, rho_min=0.5, minsup=0)
    par = int(np.asarray(miner(sub.tuples, sub.values).keep).sum())
    assert len(seq) == par


def test_packed_row_keys_canonicalise_as_rows_do():
    """The one-word row keys find the same duplicates as a row-wise
    ``np.unique``: one row per tuple in first-occurrence order, the last
    value winning; a table too wide to pack takes the row-wise path."""
    rng = np.random.default_rng(5)
    for sizes in ((7, 5, 3), (2**31 - 1, 2**31 - 1, 4)):
        rows = rng.integers(0, [4, 4, 3], (400, 3)).astype(np.int32)
        rows[:, 0] *= (sizes[0] - 1) // 3
        vals = rng.random(400).astype(np.float32)
        ctx = PolyadicContext(sizes, rows, vals)
        uniq, first, inv = np.unique(rows, axis=0, return_index=True,
                                     return_inverse=True)
        last = np.empty(uniq.shape[0], np.intp)
        last[inv.ravel()] = np.arange(len(rows))
        order = np.argsort(first, kind="stable")
        np.testing.assert_array_equal(ctx.tuples, uniq[order])
        np.testing.assert_array_equal(ctx.values, vals[last][order])
    distinct = np.arange(30, dtype=np.int32).reshape(10, 3)
    ctx = PolyadicContext((30, 30, 30), distinct,
                          np.ones(10, np.float32))
    np.testing.assert_array_equal(ctx.tuples, distinct)
