"""jit'd dispatch layer over the Pallas kernels.

Every public op here has the same calling convention as a plain jnp
function, pads ragged inputs up to the kernel's block grid, and exposes
``use_pallas=False`` fall-through to the pure-jnp oracle in ref.py.

Whether a kernel is interpreted is decided in one place,
:func:`_interpret`: the kernel body runs as interpreted HLO exactly when
the default backend is not a TPU, so tests and the CPU container execute
the kernel body; on a TPU every kernel is compiled by Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .radix_sort import radix_histogram as _radix_histogram_kernel
from .radix_sort import radix_rank as _radix_rank_kernel
from .segment_reduce import LANES
from .segment_reduce import segment_reduce as _segment_reduce_kernel
from .signature import signature as _signature_kernel
from .tricluster_density import tricluster_density as _density_kernel

#: Elements of one (8, 128) 32-bit VMEM tile: every block of the
#: triclustering kernels is a whole number of them (Mosaic refuses 1-D
#: blocks that do not match XLA's 1024-element tiling).
TILE = 8 * LANES


@functools.lru_cache(None)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(flag: Optional[bool]) -> bool:
    """Interpret mode off a TPU, compiled kernels on one.  ``flag=False``
    compiles for the TPU from a process whose backend is not one (an
    ahead-of-time compile for a described chip); a TPU never interprets."""
    if on_tpu():
        if flag:
            raise ValueError("Pallas kernels are never interpreted on a TPU")
        return False
    return True if flag is None else bool(flag)


def _block_len(t: int, bt: int) -> int:
    """Block length for a (t,) stream: whole tiles, at most ``bt``
    rounded up to a tile, and no more tiles than ``t`` needs."""
    tiles = lambda n: -(-max(int(n), 1) // TILE) * TILE  # noqa: E731
    return min(tiles(bt), tiles(t))


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def segment_reduce(w_lo: jnp.ndarray, w_hi: jnp.ndarray, first: jnp.ndarray,
                   *, bt: int = 64 * TILE, use_pallas: bool = True,
                   interpret: Optional[bool] = None):
    """Fused masked prefix sums for Stage-2 segment reductions.

    w_lo/w_hi (T,) uint32 hash weights, first (T,) bool/0-1 mask ->
    three (T,) inclusive prefix sums (uint32, uint32, int32) of the
    masked weights and of the mask — one pass instead of three
    ``segment_sum``/``cumsum`` sweeps; per-segment (or δ-window) sums
    are then boundary differences of the prefixes.  ``bt`` caps the
    block length; the kernel sees the streams as lane-dense
    (T/128, 128) int32 rows, uint32 lanes bitcast (wrapping addition is
    the same in both)."""
    if not use_pallas:
        return ref.segment_reduce_ref(w_lo, w_hi, first)
    t = w_lo.shape[0]
    blk = _block_len(t, bt)

    def rows(x):
        x = jax.lax.bitcast_convert_type(x, jnp.int32)
        return _pad_to(x, 0, blk).reshape(-1, LANES)

    lo, hi, cnt = _segment_reduce_kernel(
        rows(w_lo), rows(w_hi), rows(first.astype(jnp.int32)),
        rows=blk // LANES, interpret=_interpret(interpret))
    lo, hi, cnt = (x.reshape(-1)[:t] for x in (lo, hi, cnt))
    return (jax.lax.bitcast_convert_type(lo, jnp.uint32),
            jax.lax.bitcast_convert_type(hi, jnp.uint32), cnt)


def radix_histogram(words, shifts, widths, *, bt: int = TILE,
                    use_pallas: bool = True,
                    interpret: Optional[bool] = None):
    """One-sweep histograms of every pruned radix digit position.

    words: 1-2 msb-first (T,) uint32 packed key arrays; shifts/widths:
    static per-pass digit bit ranges -> (npass, 256) int32. The pad
    rows appended to reach the block grid all carry digit 0, so their
    count is subtracted from bucket 0 of every pass."""
    if not use_pallas:
        return ref.radix_histogram_ref(words, shifts, widths)
    t = words[0].shape[0]
    blk = _block_len(t, bt)
    pad = (-t) % blk
    hist = _radix_histogram_kernel(
        [_pad_to(w, 0, blk) for w in words], shifts=tuple(shifts),
        widths=tuple(widths), bt=blk, interpret=_interpret(interpret))
    if pad:
        hist = hist.at[:, 0].add(-pad)
    return hist


def radix_rank(digits: jnp.ndarray, starts: jnp.ndarray, *, bt: int = TILE,
               use_pallas: bool = True,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Stable radix-pass ranks ``starts[d_i] + occurrence_i``.

    digits (T,) uint32 in [0, 256), starts (256,) int32 exclusive
    bucket starts -> (T,) int32. End-padding is safe: pad positions
    only consume ranks *after* every real element's."""
    if not use_pallas:
        return ref.radix_rank_ref(digits, starts)
    t = digits.shape[0]
    blk = _block_len(t, bt)
    out = _radix_rank_kernel(_pad_to(digits, 0, blk), starts, bt=blk,
                             interpret=_interpret(interpret))
    return out[:t]


def set_signature(mask: jnp.ndarray, r: jnp.ndarray, *,
                  use_pallas: bool = True,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Order-independent set signatures: (T, E) 0/1 × (E,) u32 -> (T,) u32."""
    if not use_pallas:
        return ref.signature_ref(mask, r)
    t, e = mask.shape
    bt = 256 if t % 256 == 0 else (8 if t % 8 == 0 else 1)
    be = 512 if e % 512 == 0 else (128 if e % 128 == 0 else e)
    mp = _pad_to(_pad_to(mask, 0, bt), 1, be)
    rp = _pad_to(r, 0, be)
    out = _signature_kernel(mp, rp, bt=bt, be=be,
                            interpret=_interpret(interpret))
    return out[:t]


def tricluster_density(tensor: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray,
                       z: jnp.ndarray, *, use_pallas: bool = True,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Exact box-count numerators |X×Y×Z ∩ I| for T triclusters.

    tensor (G, M, B) 0/1; x (T, G); y (T, M); z (T, B) -> (T,) f32.
    The exact-density estimator of DESIGN.md §3 (beyond-paper: the paper's
    Alg. 7 uses the generating-tuple count approximation).
    """
    if not use_pallas:
        return ref.tricluster_density_ref(tensor, x, y, z)
    t, g = x.shape
    bt = 128 if t % 128 == 0 else (8 if t % 8 == 0 else 1)
    bg = 8 if g >= 8 else 1
    tp = _pad_to(tensor, 0, bg)
    xp = _pad_to(_pad_to(x, 0, bt), 1, bg)
    yp = _pad_to(y, 0, bt)
    zp = _pad_to(z, 0, bt)
    return _density_kernel(tp, xp, yp, zp, bt=bt, bg=bg,
                           interpret=_interpret(interpret))[:t]


def exact_density(tensor: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray,
                  z: jnp.ndarray, **kw) -> jnp.ndarray:
    """Exact densities: numerator / volume (0 if any component empty)."""
    num = tricluster_density(tensor, x, y, z, **kw)
    vol = (x.sum(-1).astype(jnp.float32) * y.sum(-1).astype(jnp.float32)
           * z.sum(-1).astype(jnp.float32))
    return num / jnp.maximum(vol, 1.0)
