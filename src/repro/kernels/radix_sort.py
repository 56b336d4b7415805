"""Pallas TPU kernels: one-sweep primitives of the 8-bit-digit radix
sort backend (``core/radix.py``, DESIGN.md §3b).

Two kernels, both streaming the tuple table through VMEM with a
sequential grid and scratch carries (the ``segment_reduce`` pattern):

* ``radix_histogram`` — ONE sweep over the packed key words builds the
  256-bucket histogram of *every* pruned digit position at once (the
  bit-plan tells us statically which bit ranges are live, so dead
  digits never cost a pass).  Histograms are permutation-invariant, so
  this runs once per sort on the original word order.  (The
  distributed shuffle's range partitioner is the same top-digit
  histogram primitive applied to the *pre-shuffle* keys — conceptually
  shared, but a separate computation on different data.)

* ``radix_rank`` — one LSD pass's stable ranks:
  ``rank[i] = bucket_start[digit_i] + #{j < i : digit_j == digit_i}``.
  Within a block the running occurrence is an exclusive one-hot prefix
  sum (Hillis–Steele ladder on the VPU); the sequential grid carries
  per-digit block totals in scratch, so the occurrence is global.
  Bucket gathers are expressed as one-hot reductions (VPU-friendly —
  no dynamic gather inside the kernel).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Digit width of the histogram formulation, and its bucket count.
HIST_DIGIT_BITS = 8
HIST_BUCKETS = 1 << HIST_DIGIT_BITS


def extract_digit(words: Sequence[jnp.ndarray], shift: int,
                  width: int) -> jnp.ndarray:
    """Bits [shift, shift+width) of msb-first packed uint32 words, as a
    uint32 digit.  ``width`` < 32 (a radix digit never spans a whole
    word of the plan).  The one bit-field reader of every sort
    formulation (``core.radix`` re-exports it), so the kernels can never
    extract a different digit than the composite and reference paths."""
    mask = jnp.uint32((1 << width) - 1)
    if len(words) == 1:
        return (words[0] >> shift) & mask
    hi, lo = words
    if shift >= 32:
        return (hi >> (shift - 32)) & mask
    if shift + width <= 32:
        return (lo >> shift) & mask
    return ((lo >> shift) | (hi << (32 - shift))) & mask


def _digit(word_refs, shift: int, width: int):
    """:func:`extract_digit` on materialised refs."""
    return extract_digit(tuple(r[...] for r in word_refs), shift, width)


def _one_hot(dig: jnp.ndarray, bt: int) -> jnp.ndarray:
    """(bt,) uint32 digits -> (bt, 256) int32 one-hot."""
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bt, HIST_BUCKETS), 1)
    return (dig[:, None] == cols).astype(jnp.int32)


def _scan_rows(x: jnp.ndarray, bt: int) -> jnp.ndarray:
    """Inclusive prefix sum along axis 0 of a (bt, 256) block."""
    s = 1
    while s < bt:
        pad = jnp.zeros((s, x.shape[1]), x.dtype)
        x = x + jnp.concatenate([pad, x[:-s]], axis=0)
        s *= 2
    return x


# ---------------------------------------------------------------------------
# Histogram sweep
# ---------------------------------------------------------------------------

def _hist_kernel(*refs, bt: int, nw: int,
                 shifts: Tuple[int, ...], widths: Tuple[int, ...]):
    word_refs, out_ref, acc_ref = refs[:nw], refs[nw], refs[nw + 1]
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for p, (shift, width) in enumerate(zip(shifts, widths)):
        oh = _one_hot(_digit(word_refs, shift, width), bt)
        acc_ref[p, :] = acc_ref[p, :] + oh.sum(axis=0)

    @pl.when(i == n - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def radix_histogram(words: Sequence[jnp.ndarray],
                    shifts: Sequence[int], widths: Sequence[int],
                    *, bt: int = 1024, interpret: bool = False):
    """All pruned digit histograms in one sweep.  words: 1-2 msb-first
    (T,) uint32 arrays, T divisible by bt -> (npass, 256) int32."""
    t = words[0].shape[0]
    assert t % bt == 0, (t, bt)
    npass = len(shifts)
    spec = pl.BlockSpec((bt,), lambda i: (i,))
    out_spec = pl.BlockSpec((npass, HIST_BUCKETS), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_hist_kernel, bt=bt, nw=len(words),
                          shifts=tuple(shifts), widths=tuple(widths)),
        grid=(t // bt,),
        in_specs=[spec] * len(words),
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((npass, HIST_BUCKETS), jnp.int32),
        scratch_shapes=[pltpu.VMEM((npass, HIST_BUCKETS), jnp.int32)],
        interpret=interpret,
        name="radix_histogram",
    )(*words)


# ---------------------------------------------------------------------------
# Per-pass stable ranks
# ---------------------------------------------------------------------------

def _rank_kernel(dig_ref, starts_ref, out_ref, carry_ref, *, bt: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    oh = _one_hot(dig_ref[...], bt)
    inc = _scan_rows(oh, bt)
    # exclusive global occurrence + bucket start, gathered one-hot-wise
    base = carry_ref[0, :] + starts_ref[...]
    rank = (oh * (inc - oh + base[None, :])).sum(axis=1)
    out_ref[...] = rank
    carry_ref[0, :] = carry_ref[0, :] + inc[bt - 1, :]


def radix_rank(digits: jnp.ndarray, starts: jnp.ndarray,
               *, bt: int = 1024, interpret: bool = False):
    """Stable LSD-pass ranks.  digits (T,) uint32 in [0, 256), starts
    (256,) int32 exclusive bucket starts, T divisible by bt ->
    (T,) int32 destination positions."""
    t = digits.shape[0]
    assert t % bt == 0, (t, bt)
    spec = pl.BlockSpec((bt,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_rank_kernel, bt=bt),
        grid=(t // bt,),
        in_specs=[spec, pl.BlockSpec((HIST_BUCKETS,), lambda i: (0,))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((t,), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, HIST_BUCKETS), jnp.int32)],
        interpret=interpret,
        name="radix_rank",
    )(digits, starts)
