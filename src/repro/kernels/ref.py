"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernel tests
``assert_allclose`` against (interpret=True on CPU, real TPU otherwise).
"""
from __future__ import annotations

from typing import Optional

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


def _attn_mask(sq: int, skv: int, q_offset: int, causal: bool,
               window: Optional[int]) -> jnp.ndarray:
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: Optional[int] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Reference attention. q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D);
    GQA via head-group broadcast. fp32 softmax accumulation."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if q_offset is None:
        q_offset = skv - sq
    if scale is None:
        scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(b, hkv, group, sq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf)
    mask = _attn_mask(sq, skv, q_offset, causal, window)
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         *, window: Optional[int] = None,
                         kv_len: Optional[int] = None,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token decode. q: (B, Hq, D); k, v: (B, Hkv, S, D). The query
    position is kv_len-1 (attends to keys [max(0, kv_len-window), kv_len))."""
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    if kv_len is None:
        kv_len = s
    out = flash_attention_ref(q[:, :, None, :], k, v, causal=True,
                              window=window, q_offset=kv_len - 1,
                              scale=scale)
    return out[:, :, 0, :]


def rmsnorm_ref(x: jnp.ndarray, w: jnp.ndarray,
                eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm rows of x (..., D) with fp32 statistics."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(
        x.dtype)
