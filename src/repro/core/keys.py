"""Packed-key sorting: the one Stage-1/Stage-3 sort path of every engine.

The paper's Hadoop shuffle *is* a sort, and sorting dominates every
engine's runtime.  ``jnp.lexsort`` already lowers to a single
``lax.sort``, but its comparator touches N+1 columns per comparison and
every payload column rides an index-gather round-trip afterwards.  This
module makes the sort hardware-shaped:

* **Bit-width planning** (``plan_mode_key`` / ``plan_context_keys``):
  each mode's lexicographic key — (other columns..., [value-lane,]
  e_k), exactly the order ``pipeline.sort_mode`` sorts by — is laid out
  as bit-fields of one conceptual uint64, entity widths sized
  ``ceil(log2(|A_j|))`` from the context's mode cardinalities.  Every
  mode's key covers all N columns (plus the value lane for many-valued
  contexts), so ``total_bits`` — and therefore ``fits`` — is a property
  of the *context*, not of the mode.

* **Value-lane cardinality pruning** (``value_slots``): by default the
  value lane is the 32-bit order-preserving float encoding, but when
  the caller knows the context's distinct-value *domain* up front
  (batch/many-valued/distributed engines — anything that sees the whole
  value column before packing), the lane stores the value's **rank** in
  the sorted domain instead: ``ceil(log2 n_distinct)`` bits, an
  order-isomorphic code, so every sort order, segment boundary and
  δ-window is unchanged while the radix backend prunes its pass
  schedule to the bits that actually vary (a movielens-like 5-star
  domain is a 3-bit lane — the NOAC key drops from two words to one).
  The streaming engine keeps the float lane: its incremental runs must
  stay mergeable when later chunks introduce unseen values.

* **One packer, two homes**: ``pack_host`` produces the np.uint64 the
  streaming engine merges sorted runs over; ``pack_device`` produces the
  same word as one uint32 (``total_bits`` ≤ 32) or an msb-first
  (hi, lo) uint32 pair — jax runs in 32-bit mode, so the device never
  materialises a real uint64, but ``(hi << 32) | lo == pack_host(...)``
  bit-for-bit.  Host-merged streaming permutations and device sorts
  therefore order identically by construction.

* **Single sort, payloads carried** (``sort_with_payload``): one stable
  sort whose key is the 1–2 packed words — by default the bit-plan-
  pruned LSD radix backend of ``core.radix`` (DESIGN.md §3b), with
  ``backend='lax'`` keeping the one-``lax.sort`` comparison path whose
  payload columns ride as sort operands.  Segment starts and first-
  occurrence flags downstream become 1–2 word comparisons
  (``drop_low_bits`` strips the [value,] e_k suffix to recover the
  subrelation key).

* **Fallback**: a context whose key exceeds 64 bits simply reports
  ``fits=False`` and the pipeline keeps the N+1-column lexsort path
  behind the same API — no engine has a packed-only code path.

Caveat shared with the streaming engine's original host codec: the
order-preserving float32 encoding (``float_sort_bits``) distinguishes
-0.0 from +0.0 and has no defined order for NaNs; value columns are
expected to be finite and normalised (DESIGN.md §3a).  The rank-coded
lane compares -0.0 == +0.0 (like the column lexsort fallback) but still
requires finite values.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: ``Field.src`` sentinel for the float-value lane of many-valued keys.
VALUE = -1

_SIGN = 0x80000000
_FULL = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Order-preserving float32 encoding (host + device, bit-identical)
# ---------------------------------------------------------------------------

def float_sort_bits_host(v: np.ndarray) -> np.ndarray:
    """Order-preserving uint32 encoding of finite float32 values."""
    u = np.ascontiguousarray(v, np.float32).view(np.uint32)
    return u ^ np.where(u & _SIGN, np.uint32(_FULL), np.uint32(_SIGN))


def float_sort_bits(v: jnp.ndarray) -> jnp.ndarray:
    """Device twin of :func:`float_sort_bits_host`."""
    u = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    return u ^ jnp.where((u & jnp.uint32(_SIGN)) != 0,
                         jnp.uint32(_FULL), jnp.uint32(_SIGN))


def float_from_sort_bits(u: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`float_sort_bits` (the encoding is a bijection),
    letting shuffle owners recover value columns from shipped keys."""
    orig = u ^ jnp.where((u & jnp.uint32(_SIGN)) != 0,
                         jnp.uint32(_SIGN), jnp.uint32(_FULL))
    return jax.lax.bitcast_convert_type(orig, jnp.float32)


# ---------------------------------------------------------------------------
# Bit-width planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Field:
    """One bit-field of a packed key: tuple column ``src`` (or ``VALUE``)
    at ``offset`` bits from the LSB, ``width`` bits wide."""
    src: int
    offset: int
    width: int


def entity_bits(size: int) -> int:
    """Bits needed for ids 0..size-1 (≥ 1, matching the streaming codec)."""
    return max(1, int(np.ceil(np.log2(max(int(size), 2)))))


def value_lane_bits(value_slots: Optional[int]) -> int:
    """Width of the value lane: rank bits for a known ``value_slots``-sized
    domain, the full float32 sort-bit encoding otherwise."""
    return 32 if value_slots is None else entity_bits(value_slots)


def value_domain_host(values) -> np.ndarray:
    """Sorted distinct float32 values — THE lane-pruning domain (one
    definition, so host packers, engines and benchmarks can never
    disagree on dedup/ordering semantics, e.g. -0.0 == +0.0)."""
    return np.unique(np.asarray(values, np.float32))


@dataclasses.dataclass(frozen=True)
class ModeKeyPlan:
    """Bit layout of mode ``k``'s sort key (msb-first ``fields``)."""
    k: int
    sizes: Tuple[int, ...]
    with_values: bool
    fields: Tuple[Field, ...]
    total_bits: int
    e_bits: int          # width of the trailing e_k field
    seg_shift: int       # bits to drop to recover the subrelation key
    fits: bool           # total_bits <= 64: packed path available
    value_bits: int = 32  # value-lane width (< 32: rank-coded, needs domain)

    @property
    def words(self) -> int:
        """Device words (uint32) holding the key: 1 or 2."""
        return 1 if self.total_bits <= 32 else 2

    @property
    def e_mask(self) -> int:
        return (1 << self.e_bits) - 1

    # -- value-lane encoding ------------------------------------------------

    def value_lane_host(self, values: np.ndarray,
                        domain: Optional[np.ndarray] = None) -> np.ndarray:
        """uint32 lane codes for float32 ``values``: sort bits, or ranks
        in the sorted distinct-value ``domain`` (pruned plans)."""
        if self.value_bits == 32:
            return float_sort_bits_host(values)
        if domain is None:
            raise ValueError("rank-coded value lane needs the domain")
        return np.searchsorted(np.asarray(domain, np.float32),
                               np.asarray(values, np.float32),
                               side="left").astype(np.uint32)

    def value_lane(self, values: jnp.ndarray,
                   domain: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Device twin of :meth:`value_lane_host` (bit-identical)."""
        if self.value_bits == 32:
            return float_sort_bits(values)
        if domain is None:
            raise ValueError("rank-coded value lane needs the domain")
        return jnp.searchsorted(domain.astype(jnp.float32),
                                values.astype(jnp.float32),
                                side="left").astype(jnp.uint32)

    # -- packing ------------------------------------------------------------

    def pack_host(self, rows: np.ndarray,
                  values: Optional[np.ndarray] = None,
                  domain: Optional[np.ndarray] = None) -> np.ndarray:
        """(L, N) int32 rows [+ (L,) float32 values] -> (L,) uint64 keys."""
        key = np.zeros(rows.shape[0], np.uint64)
        lane = (self.value_lane_host(values, domain)
                if self.with_values else None)
        for f in self.fields:
            v = lane if f.src == VALUE else rows[:, f.src].astype(np.uint32)
            key = (key << np.uint64(f.width)) | v.astype(np.uint64)
        return key

    def pack_device(self, tuples: jnp.ndarray,
                    values: Optional[jnp.ndarray] = None,
                    domain: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, ...]:
        """Device packing: msb-first uint32 words ((hi, lo) or (lo,)).

        ``(hi << 32) | lo`` equals :meth:`pack_host` bit-for-bit; all
        shifts are static so this lowers to a handful of fused ALU ops
        (plus one small binary search for rank-coded value lanes)."""
        t = tuples.shape[0]
        lo = jnp.zeros((t,), jnp.uint32)
        hi = jnp.zeros((t,), jnp.uint32)
        lane = self.value_lane(values, domain) if self.with_values else None
        for f in self.fields:
            v = lane if f.src == VALUE else tuples[:, f.src].astype(jnp.uint32)
            if f.offset < 32:
                lo = lo | (v << f.offset if f.offset else v)
                if f.offset + f.width > 32:
                    hi = hi | (v >> (32 - f.offset))
            else:
                hi = hi | (v << (f.offset - 32) if f.offset > 32 else v)
        return (hi, lo) if self.words == 2 else (lo,)

    def extract_entity(self, words: Sequence[jnp.ndarray]) -> jnp.ndarray:
        """Recover the e_k column from packed words (e_k is the LSB field)."""
        return (words[-1] & jnp.uint32(self.e_mask)).astype(jnp.int32)

    def extract_values(self, words: Sequence[jnp.ndarray],
                       domain: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Recover the float32 value column from packed words (many-valued
        plans only; the value lane sits at bit offset ``e_bits``): sort
        bits invert bijectively, rank lanes gather from the domain."""
        if not self.with_values:
            raise ValueError("plan has no value lane")
        if self.value_bits == 32:
            s = self.e_bits                 # 1 <= s <= 31, value needs 2 words
            u = (words[-1] >> s) | (words[-2] << (32 - s))
            return float_from_sort_bits(u)
        if domain is None:
            raise ValueError("rank-coded value lane needs the domain")
        from .radix import extract_digit
        rank = extract_digit(words, self.e_bits, self.value_bits)
        return domain.astype(jnp.float32)[rank.astype(jnp.int32)]

    def delta_query_words(self, words: Sequence[jnp.ndarray],
                          lane: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        """Each key's words with the value lane replaced by ``lane``
        (uint32 codes from :meth:`value_lane`'s encoding) and e_k zeroed
        — the δ-window *lower-bound* query key (OR ``e_mask`` onto the
        last word for the upper bound).  Because the subrelation prefix
        leads the key, a global search with these queries self-clamps to
        the tuple's own segment."""
        if not self.with_values:
            raise ValueError("plan has no value lane")
        eb, ss = self.e_bits, self.seg_shift
        part_lo = lane << eb                # uint32 keeps the low word
        part_hi = lane >> (32 - eb)         # 0 unless the lane spans words
        if len(words) == 1:
            keep = jnp.uint32(~((1 << ss) - 1) & 0xFFFFFFFF)
            return ((words[0] & keep) | part_lo,)
        hi, lo = words
        if ss >= 32:                        # value+e tail fills the low word
            keep = jnp.uint32(~((1 << (ss - 32)) - 1) & 0xFFFFFFFF)
            return ((hi & keep) | part_hi, part_lo)
        keep = jnp.uint32(~((1 << ss) - 1) & 0xFFFFFFFF)
        return (hi, (lo & keep) | part_lo)


def plan_mode_key(sizes: Sequence[int], k: int, with_values: bool,
                  value_slots: Optional[int] = None) -> ModeKeyPlan:
    """Lay out mode ``k``'s sort key (others..., [value,] e_k) msb-first.

    ``value_slots`` — the context's distinct-value count, when known —
    prunes the value lane to rank width (see module docstring)."""
    sizes = tuple(int(s) for s in sizes)
    bits = [entity_bits(s) for s in sizes]
    vb = value_lane_bits(value_slots)
    order = [j for j in range(len(sizes)) if j != k]
    order += ([VALUE] if with_values else []) + [k]
    widths = [vb if j == VALUE else bits[j] for j in order]
    total = sum(widths)
    fields, off = [], total
    for src, w in zip(order, widths):
        off -= w
        fields.append(Field(src, off, w))
    return ModeKeyPlan(
        k=k, sizes=sizes, with_values=with_values, fields=tuple(fields),
        total_bits=total, e_bits=bits[k],
        seg_shift=bits[k] + (vb if with_values else 0), fits=total <= 64,
        value_bits=vb)


def plan_context_keys(sizes: Sequence[int], with_values: bool,
                      value_slots: Optional[int] = None
                      ) -> Tuple[ModeKeyPlan, ...]:
    """One plan per mode.  All plans share ``total_bits``/``fits`` (every
    mode's key covers all columns), so ``plans[0].fits`` decides the
    context's sort path."""
    return tuple(plan_mode_key(sizes, k, with_values, value_slots)
                 for k in range(len(sizes)))


# ---------------------------------------------------------------------------
# Device-side sorting primitives
# ---------------------------------------------------------------------------

def drop_low_bits(words: Tuple[jnp.ndarray, ...],
                  shift: int) -> Tuple[jnp.ndarray, ...]:
    """Words representing ``key >> shift`` (msb-first; order-preserving),
    used to compare subrelation keys without re-materialising columns."""
    if shift == 0:
        return words
    if len(words) == 1:
        return (words[0] >> shift,)
    hi, lo = words
    if shift == 32:
        return (hi,)
    if shift > 32:
        return (hi >> (shift - 32),)
    return (hi, lo >> shift)


def sort_with_payload(words: Sequence[jnp.ndarray],
                      payloads: Sequence[jnp.ndarray],
                      backend: str = "radix",
                      live_bits: Optional[int] = None,
                      use_pallas: bool = False):
    """Stable sort keyed on the packed words with payload columns
    carried along.  The default backend is the bit-plan-pruned LSD
    radix of ``core.radix`` (``live_bits`` prunes the pass schedule to
    the key's live bit count; ``use_pallas`` selects its histogram-
    kernel formulation).  ``backend='lax'`` keeps the one-``lax.sort``
    comparison path, whose comparator reads 1-2 words and carries the
    payloads as sort operands.  Both are bit-identical, permutation
    included (``tests/test_radix_property.py``).

    Returns (sorted_words, sorted_payloads), both tuples."""
    if backend == "radix":
        from . import radix as RX
        return RX.sort_with_payload_radix(
            words, payloads, live_bits or 32 * len(words), use_pallas)
    nw = len(words)
    out = jax.lax.sort(tuple(words) + tuple(payloads), num_keys=nw,
                       is_stable=True)
    return out[:nw], out[nw:]


def search_steps(t: int) -> int:
    """Dependent steps of a binary search over ``t`` sorted keys —
    each one a gather of every query's probe (:func:`search_words`)."""
    return max(1, int(np.ceil(np.log2(max(t, 2)))) + 1)


def search_words(s_words: Sequence[jnp.ndarray],
                 q_words: Sequence[jnp.ndarray], upper: bool) -> jnp.ndarray:
    """Vectorised binary search over sorted packed keys.  Returns, per
    query, the first index whose key is > the query (``upper``) or >= it
    (lower bound); T if none.  Keys compare lexicographically over the
    msb-first word tuples."""
    t = s_words[0].shape[0]
    iters = search_steps(t)
    lo = jnp.zeros(q_words[0].shape, jnp.int32)
    hi = jnp.full(q_words[0].shape, t, jnp.int32)
    for _ in range(iters):
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, t - 1)
        if len(s_words) == 2:
            dh, dl = s_words[0][midc], s_words[1][midc]
            qh, ql = q_words
            go_right = ((dh < qh) | ((dh == qh) & (dl <= ql)) if upper
                        else (dh < qh) | ((dh == qh) & (dl < ql)))
        else:
            d, q = s_words[0][midc], q_words[0]
            go_right = (d <= q) if upper else (d < q)
        go_right = go_right & (lo < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right | (lo >= hi), hi, mid)
    return lo
