"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernel tests
``assert_allclose`` against (interpret=True on CPU, real TPU otherwise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .._compat import cumulative
from .radix_sort import HIST_BUCKETS, extract_digit


def tricluster_density_ref(tensor: jnp.ndarray, x: jnp.ndarray,
                           y: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Exact tricluster box-count numerators.

    tensor: (G, M, B) 0/1; x: (T, G); y: (T, M); z: (T, B).
    Returns (T,) float32: |X_t × Y_t × Z_t ∩ I|.
    """
    t32 = tensor.astype(jnp.float32)
    num = jnp.einsum("tg,tm,tb,gmb->t", x.astype(jnp.float32),
                     y.astype(jnp.float32), z.astype(jnp.float32), t32)
    return num


def signature_ref(mask: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Order-independent set signatures: sig[t] = Σ_e mask[t,e]·r[e] mod 2³².

    mask: (T, E) bool/0-1; r: (E,) uint32. Returns (T,) uint32.
    """
    m = mask.astype(jnp.uint32)
    return (m * r[None, :]).sum(axis=1, dtype=jnp.uint32)


def segment_reduce_ref(w_lo: jnp.ndarray, w_hi: jnp.ndarray,
                       first: jnp.ndarray):
    """Fused masked prefix sums: inclusive cumsums of first-occurrence-
    masked uint32 hash weights and of the mask itself.

    w_lo, w_hi: (T,) uint32; first: (T,) bool/0-1.
    Returns ((T,) uint32, (T,) uint32, (T,) int32).
    """
    f = first.astype(bool)
    lo = cumulative(jnp.where(f, w_lo, jnp.uint32(0)), jax.lax.add)
    hi = cumulative(jnp.where(f, w_hi, jnp.uint32(0)), jax.lax.add)
    cnt = cumulative(f.astype(jnp.int32), jax.lax.add)
    return lo, hi, cnt


def radix_histogram_ref(words, shifts, widths):
    """All pruned digit histograms of the packed key words.

    words: 1-2 msb-first (T,) uint32 arrays; shifts/widths: the radix
    plan's per-pass digit bit ranges. Returns (npass, 256) int32.
    """
    rows = []
    for shift, width in zip(shifts, widths):
        d = extract_digit(words, shift, width).astype(jnp.int32)
        rows.append(jnp.zeros((HIST_BUCKETS,), jnp.int32).at[d].add(1))
    return jnp.stack(rows)


def radix_rank_ref(digits: jnp.ndarray, starts: jnp.ndarray) -> jnp.ndarray:
    """Stable LSD-pass ranks: rank[i] = starts[d_i] + #{j<i : d_j==d_i}.

    digits: (T,) uint32 in [0, 256); starts: (256,) int32 exclusive
    bucket starts. Returns (T,) int32 destination positions.
    """
    oh = (digits[:, None] ==
          jnp.arange(HIST_BUCKETS, dtype=jnp.uint32)[None, :])
    oh = oh.astype(jnp.int32)
    occ = cumulative(oh, jax.lax.add, axis=0) - oh
    return (oh * (occ + starts[None, :])).sum(axis=1)
