"""Pallas TPU kernel: fused masked-weight prefix sums for Stage-2 segment
reductions.

Every component operator of the mining pipeline (prime cumulus and
δ-range alike) reduces the same three per-position streams over sorted
order: two uint32 hash-weight lanes and the first-occurrence counter,
all masked by the first-occurrence flag.  The jnp path spends three
separate ``segment_sum``/``cumsum`` sweeps on them; this kernel computes
the three *inclusive prefix sums* in one pass —

    out_lo[i]  = Σ_{j<=i} first[j] ? w_lo[j] : 0      (mod 2³²)
    out_hi[i]  = Σ_{j<=i} first[j] ? w_hi[j] : 0      (mod 2³²)
    out_cnt[i] = Σ_{j<=i} first[j]

— after which any segment or δ-window reduction is two boundary gathers
(``pref[b] - pref[a]``; modular arithmetic makes the differences exact).

Layout: the (T,) streams arrive as lane-dense (T/128, 128) int32 arrays
(uint32 lanes bitcast; two's-complement addition wraps exactly like
uint32), tiled in (rows, 128) blocks, prefix order row-major.  Within a
block the scan is two Hillis–Steele ladders on the VPU built from
``pltpu.roll`` and iota masks — Mosaic refuses the unaligned lane
concatenates of a 1-D ladder: first along the 128 lanes of each row,
then along the rows over the row totals.  The sequential TPU grid
carries the running totals in VMEM as lane-broadcast (1, 128) vectors,
so no vector element is ever moved to a scalar and arbitrarily long
tuple tables stream through VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _ladder(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive prefix sum of ``x`` along ``axis``: log2 steps of
    ``x + (shifted x, zero where the shift wrapped)``."""
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < n:
        x = x + jnp.where(idx >= s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def _block_scan(x: jnp.ndarray):
    """Row-major inclusive prefix sum of an (rows, 128) int32 block, and
    the block total broadcast over a (1, 128) row."""
    rows = x.shape[0]
    x = _ladder(x, 1)
    row_tot = jnp.broadcast_to(x[:, LANES - 1:], x.shape)
    row_inc = _ladder(row_tot, 0)
    return x + (row_inc - row_tot), row_inc[rows - 1:, :]


def _kernel(wlo_ref, whi_ref, f_ref, olo_ref, ohi_ref, ocnt_ref,
            clo_ref, chi_ref, ccnt_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        clo_ref[...] = jnp.zeros_like(clo_ref)
        chi_ref[...] = jnp.zeros_like(chi_ref)
        ccnt_ref[...] = jnp.zeros_like(ccnt_ref)

    f = f_ref[...] != 0
    for x, out_ref, carry_ref in (
            (jnp.where(f, wlo_ref[...], 0), olo_ref, clo_ref),
            (jnp.where(f, whi_ref[...], 0), ohi_ref, chi_ref),
            (f.astype(jnp.int32), ocnt_ref, ccnt_ref)):
        scan, total = _block_scan(x)
        carry = carry_ref[...]
        out_ref[...] = scan + carry
        carry_ref[...] = carry + total


def segment_reduce(w_lo: jnp.ndarray, w_hi: jnp.ndarray, first: jnp.ndarray,
                   *, rows: int, interpret: bool = False):
    """w_lo/w_hi/first (R, 128) int32 (first 0/1) -> three (R, 128) int32
    row-major inclusive masked prefix sums.  R must divide by ``rows``,
    a multiple of 8 (one (8, 128) int32 tile)."""
    r = w_lo.shape[0]
    assert w_lo.shape[1] == LANES and r % rows == 0 and rows % 8 == 0, (
        w_lo.shape, rows)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = jax.ShapeDtypeStruct((r, LANES), jnp.int32)
    return pl.pallas_call(
        _kernel,
        grid=(r // rows,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[out, out, out],
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.int32)] * 3,
        # the carries need the blocks in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="segment_reduce",
    )(w_lo, w_hi, first)
