"""Shared Stage-1/2/3 mining pipeline — the one skeleton behind every
engine (DESIGN.md §3, "Unified pipeline").

The paper's M/R algorithm is the *same* three jobs for the prime OAC,
multimodal (N-ary) and many-valued (NOAC, §3.2/§4.3) variants; only the
per-key *component operator* differs.  This module is that factoring:

  Stage 1  ``sort_mode``            per-mode sort of the tuple table by
           the mode's shuffle key (the N-1 "other" columns, plus the
           value column for many-valued contexts) and segmentation of
           the sorted order — the Hadoop shuffle-by-subrelation as a
           sort.  When the key fits 64 bits (``core.keys`` plans), the
           sort is ONE stable sort over the packed key word(s) — the
           bit-plan-pruned radix backend (``core.radix``) by default,
           or one ``lax.sort`` with payloads as sort operands
           (``sort_backend='lax'``); otherwise the N+1-column lexsort
           fallback runs behind the same API.
  comp-op  ``prime_components``     cumulus = the whole key segment.
           ``delta_components``     δ-range inside the key segment
                                    (rank-threshold scans or two
                                    vectorised binary searches).
           This is the only place the variants differ.
  Stage 2  ``mix_signatures``       gather per-mode ⟨signature,
           cardinality⟩ aggregates back to each generating tuple.
  Stage 3  ``stage3_dedup``         order-independent dedup + distinct
           generating-tuple counts on 2×32-bit set signatures, via one
           more sort; density is the paper-faithful Alg. 7 estimate
           ``#distinct generating tuples / volume``.

``mine_tuples`` composes the stages into the full jit-able pipeline;
``batch``, ``distributed``, ``streaming`` and ``manyvalued`` are thin
drivers around it (single shard / shard_map mesh / incremental sorted
runs).  All signatures are *order-independent modular sums of
first-occurrence-masked hash weights*, which makes every engine
duplicate-idempotent (M/R at-least-once, §5.1) and lets the distributed
and streaming engines reproduce single-shard results bit-exactly.

Shapes are static in ``T`` (tuples) and ``N`` (arity), so each engine
jits once per context shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# the shared shard_map call (canonical home: repro._compat)
from .._compat import shard_map  # noqa: F401  (re-export for the engines)
from .._compat import cumulative
from ..kernels import ops as kops
from ..obs.phases import phase
from . import keys as K
from . import radix as RX


# ---------------------------------------------------------------------------
# Hashing
# ---------------------------------------------------------------------------

# Per-mode multipliers for mixing mode signatures into a cluster signature.
# Odd constants (invertible mod 2^32) from splitmix64 / Weyl sequences.
_MIX = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
                 0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09],
                dtype=np.uint32)


def mode_hash_vectors(sizes: Sequence[int], seed: int = 0x5EED):
    """Two independent uint32 hash vectors per mode (host-side, fixed seed).

    Every engine built from the same (sizes, seed) produces bit-identical
    cluster signatures — the cross-backend parity guarantee."""
    rng = np.random.Generator(np.random.Philox(seed))
    return [
        (rng.integers(1, 2**32, size=n, dtype=np.uint32),
         rng.integers(1, 2**32, size=n, dtype=np.uint32))
        for n in sizes
    ]


def mix_signatures(per_mode_lo, per_mode_hi):
    """Combine per-mode set signatures into one 2×32-bit cluster signature."""
    lo = jnp.zeros_like(per_mode_lo[0])
    hi = jnp.zeros_like(per_mode_hi[0])
    for k, (slo, shi) in enumerate(zip(per_mode_lo, per_mode_hi)):
        lo = lo + jnp.uint32(_MIX[k % len(_MIX)]) * slo
        hi = hi + jnp.uint32(_MIX[(k + 3) % len(_MIX)]) * shi
    # final avalanche
    lo = (lo ^ (lo >> 16)) * jnp.uint32(0x7FEB352D)
    hi = (hi ^ (hi >> 15)) * jnp.uint32(0x846CA68B)
    return lo, hi


# ---------------------------------------------------------------------------
# Sorting / segmentation primitives
# ---------------------------------------------------------------------------

def lex_perm(columns: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Permutation sorting rows lexicographically by ``columns`` (first column
    is the most significant key)."""
    return jnp.lexsort(tuple(reversed(list(columns))))


def segment_starts(sorted_key_cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Boolean start-of-segment flags for already-sorted key columns."""
    t = sorted_key_cols[0].shape[0]
    change = jnp.zeros((t,), bool).at[0].set(True)
    for c in sorted_key_cols:
        change = change | jnp.concatenate(
            [jnp.ones((1,), bool), c[1:] != c[:-1]])
    return change


def segment_bounds(flags: jnp.ndarray):
    """Per sorted position: the [a, b) window of its own run, where
    ``flags`` marks run starts (``flags[..., 0]`` must be True); a
    stack of flag rows ``(..., T)`` is scanned row by row.

    Two O(T) scans — a forward cummax and a reverse cummin — instead of
    the segment-id cumsum + ``segment_min``/``segment_sum`` scatter
    formulation, which dominates Stage-1 time on scatter-unfriendly
    backends."""
    t, axis = flags.shape[-1], flags.ndim - 1
    pos = jnp.arange(t, dtype=jnp.int32)
    a = cumulative(jnp.where(flags, pos, 0), jax.lax.max, axis=axis)
    suff = cumulative(jnp.where(flags, pos, jnp.int32(t)), jax.lax.min,
                      axis=axis, reverse=True)
    b = jnp.concatenate([suff[..., 1:],
                         jnp.full(flags.shape[:-1] + (1,), t, jnp.int32)],
                        axis=axis)
    return a, b


@dataclasses.dataclass
class SortedMode:
    """Stage-1 output for one mode: the tuple table sorted by the mode's
    shuffle key and segmented by it.  All arrays have length T and are
    indexed by *sorted* position; ``seg_a``/``seg_b`` delimit each
    position's own key segment as a half-open window of sorted order."""
    perm: jnp.ndarray         # sorted order of tuples
    inv: jnp.ndarray          # inverse permutation (original → sorted pos)
    seg_a: jnp.ndarray        # segment start per sorted position
    seg_b: jnp.ndarray        # segment end (exclusive) per sorted position
    sorted_e: jnp.ndarray     # mode-k entity column under perm
    sorted_vals: Optional[jnp.ndarray]  # values under perm (None: prime)
    first_occ: jnp.ndarray    # per sorted position: first of its
                              # identical (key[, value], e) run
    sorted_words: Optional[tuple] = None  # packed key words (packed path)
    plan: Optional[K.ModeKeyPlan] = None  # the key layout (packed path)

jax.tree_util.register_dataclass(
    SortedMode, data_fields=["perm", "inv", "seg_a", "seg_b",
                             "sorted_e", "sorted_vals", "first_occ",
                             "sorted_words"],
    meta_fields=["plan"])


def mode_key_columns(tuples: jnp.ndarray, k: int,
                     values: Optional[jnp.ndarray] = None):
    """Mode ``k``'s lexicographic sort-key columns — (others..., [value,]
    e_k) — as (others, tail) lists.  THE column order of Stage 1's sort
    (shared by ``sort_mode`` and the benchmark probes, so what the
    benchmarks time is what the pipeline runs)."""
    n = tuples.shape[1]
    others = [tuples[:, j] for j in range(n) if j != k]
    tail = ([values] if values is not None else []) + [tuples[:, k]]
    return others, tail


def mode_sort_perm(tuples: jnp.ndarray, k: int,
                   values: Optional[jnp.ndarray] = None,
                   plan: Optional[K.ModeKeyPlan] = None,
                   sort_backend: str = "radix",
                   use_pallas: bool = False,
                   value_domain: Optional[jnp.ndarray] = None):
    """Exactly Stage 1's sort — the part the sort backend swaps: key
    packing + the stable word sort (packed plans) or the column lexsort.
    Returns (perm, sorted_words-or-None).  ``sort_mode`` builds on this;
    ``benchmarks/packed.py`` times it in isolation (``stage1_sort_ms``)."""
    t = tuples.shape[0]
    if plan is not None and plan.fits:
        words = plan.pack_device(tuples, values, domain=value_domain)
        s_words, (perm,) = K.sort_with_payload(
            words, (jnp.arange(t, dtype=jnp.int32),),
            backend=sort_backend, live_bits=plan.total_bits,
            use_pallas=use_pallas)
        return perm, s_words
    others, tail = mode_key_columns(tuples, k, values)
    return lex_perm(others + tail), None


def sort_mode(tuples: jnp.ndarray, k: int,
              values: Optional[jnp.ndarray] = None,
              perm: Optional[jnp.ndarray] = None,
              plan: Optional[K.ModeKeyPlan] = None,
              sort_backend: str = "radix",
              use_pallas: bool = False,
              value_domain: Optional[jnp.ndarray] = None) -> SortedMode:
    """Stage 1 for mode k.  Sort key: (other columns..., [value,] e_k), so
    duplicates of a (key[, value], e) pair land adjacent and the
    ``first_occ`` mask makes all downstream sums duplicate-idempotent.

    ``plan`` (a fitting ``keys.ModeKeyPlan``) selects the packed-key
    path: one stable sort on 1–2 uint32 key words — the bit-plan-pruned
    radix backend by default, or one ``lax.sort`` carrying the
    permutation iota as payload (``sort_backend='lax'``); the entity
    and value columns are decoded from the sorted key's bit-fields, and
    segment/first-occurrence flags are 1–2 word comparisons.  Without a
    plan (or when the key exceeds 64 bits) the N+1-column lexsort
    fallback runs.  All paths are bit-identical (the packed word order
    *is* the lexicographic column order, and every sort is stable).

    ``perm`` short-circuits the sort with a precomputed permutation (the
    streaming engine maintains one by merging sorted runs)."""
    t, n = tuples.shape
    s_words = None
    if plan is not None and plan.fits:
        if perm is None:
            perm, s_words = mode_sort_perm(tuples, k, values, plan,
                                           sort_backend, use_pallas,
                                           value_domain)
        else:
            words = plan.pack_device(tuples, values, domain=value_domain)
            s_words = tuple(w[perm] for w in words)
        # the sorted value column is a bit-field of the sorted key — decode
        # it instead of carrying a float payload through the sort
        s_vals = (plan.extract_values(s_words, domain=value_domain)
                  if values is not None else None)
        s_e = plan.extract_entity(s_words)
        seg_flag = segment_starts(K.drop_low_bits(s_words, plan.seg_shift))
        first_occ = segment_starts(s_words)
    else:
        plan = None
        others, tail = mode_key_columns(tuples, k, values)
        if perm is None:
            perm = lex_perm(others + tail)
        s_others = [c[perm] for c in others]
        s_e = tuples[perm, k]
        s_vals = values[perm] if values is not None else None
        seg_flag = segment_starts(s_others)
        first_occ = segment_starts(
            s_others + ([s_vals] if s_vals is not None else []) + [s_e])
    seg_a, seg_b = segment_bounds(seg_flag)
    pos = jnp.arange(t, dtype=jnp.int32)
    inv = jnp.zeros((t,), jnp.int32).at[perm].set(pos)
    return SortedMode(perm, inv, seg_a, seg_b, s_e, s_vals, first_occ,
                      s_words, plan)


# ---------------------------------------------------------------------------
# Component operators (the pluggable part)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ModeComponents:
    """One mode's component per tuple, in *original* tuple order.

    ``range_lo``/``range_hi`` delimit the component as a half-open window
    of the mode's sorted order — the cumulus tables of the paper shrink
    from O(|I|·Σ|A_j|) dictionary bytes to O(|I|) ranges."""
    sig_lo: jnp.ndarray     # order-independent set hash of the component
    sig_hi: jnp.ndarray
    card: jnp.ndarray       # distinct entity count
    range_lo: jnp.ndarray   # window start in sorted order
    range_hi: jnp.ndarray   # window end (exclusive)

jax.tree_util.register_dataclass(
    ModeComponents, data_fields=["sig_lo", "sig_hi", "card", "range_lo",
                                 "range_hi"],
    meta_fields=[])


def masked_prefix(w_lo: jnp.ndarray, w_hi: jnp.ndarray,
                  first_occ: jnp.ndarray, use_pallas: bool = False):
    """Exclusive (length T+1) prefix sums of first-occurrence-masked hash
    weights and of the mask — the one segment-reduction sweep both
    component operators consume (``kernels/segment_reduce`` fuses the
    three sums into a single pass; ``use_pallas=False`` runs the
    bit-identical jnp oracle)."""
    lo, hi, cnt = kops.segment_reduce(w_lo, w_hi, first_occ,
                                      use_pallas=use_pallas)
    zu = jnp.zeros((1,), jnp.uint32)
    return (jnp.concatenate([zu, lo]), jnp.concatenate([zu, hi]),
            jnp.concatenate([jnp.zeros((1,), jnp.int32), cnt]))


def prime_components(sm: SortedMode, r_lo: jnp.ndarray, r_hi: jnp.ndarray,
                     use_pallas: bool = False) -> ModeComponents:
    """Prime cumulus operator (Alg. 2+3): the component of a tuple along a
    mode is its *whole* key segment.  Signatures/cardinalities are
    boundary differences of the fused masked prefix sums (modular uint32
    arithmetic makes them exactly the segment sums)."""
    pref_lo, pref_hi, pref_cnt = masked_prefix(
        r_lo[sm.sorted_e], r_hi[sm.sorted_e], sm.first_occ, use_pallas)
    a = sm.seg_a[sm.inv]
    b = sm.seg_b[sm.inv]
    return ModeComponents(pref_lo[b] - pref_lo[a], pref_hi[b] - pref_hi[a],
                          pref_cnt[b] - pref_cnt[a], a, b)


def bsearch(vals: jnp.ndarray, lo0: jnp.ndarray, hi0: jnp.ndarray,
            target: jnp.ndarray, leq: bool) -> jnp.ndarray:
    """Vectorised binary search. Returns, per query, the first index in
    [lo0, hi0) where vals[idx] >= target (leq=False: lower bound) or
    vals[idx] > target (leq=True: upper bound); hi0 if none."""
    t = vals.shape[0]
    iters = max(1, int(np.ceil(np.log2(max(t, 2)))) + 1)
    lo, hi = lo0, hi0
    for _ in range(iters):
        mid = (lo + hi) // 2
        v = vals[jnp.clip(mid, 0, t - 1)]
        go_right = (v <= target) if leq else (v < target)
        go_right = go_right & (lo < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right | (lo >= hi), hi, mid)
    return lo


def delta_components(sm: SortedMode, r_lo: jnp.ndarray, r_hi: jnp.ndarray,
                     values: jnp.ndarray, delta: float,
                     use_pallas: bool = False,
                     value_domain: Optional[jnp.ndarray] = None
                     ) -> ModeComponents:
    """δ-range operator (NOAC, §3.2/§4.3): the component of a tuple with
    value v0 is the contiguous value-window [v0-δ, v0+δ] *inside* its key
    segment, bounded by :func:`_delta_bounds`.  Signatures are
    differences of the fused masked prefix sums (modular arithmetic
    makes range differences exact)."""
    pref_lo, pref_hi, pref_cnt = masked_prefix(
        r_lo[sm.sorted_e], r_hi[sm.sorted_e], sm.first_occ, use_pallas)
    with jax.named_scope("delta_search"):
        lo_idx, hi_idx = _delta_bounds(sm, values, delta, value_domain)
    return ModeComponents(pref_lo[hi_idx] - pref_lo[lo_idx],
                          pref_hi[hi_idx] - pref_hi[lo_idx],
                          pref_cnt[hi_idx] - pref_cnt[lo_idx],
                          lo_idx.astype(jnp.int32),
                          hi_idx.astype(jnp.int32))


def delta_bounds_path(t: int, value_slots: Optional[int]) -> str:
    """How :func:`_delta_bounds` finds the δ-windows of a ``t``-row
    table whose value lane is rank-coded over ``value_slots`` domain
    entries (None: no rank-coded lane).  ``"runs"``: one pair of
    segment scans per rank threshold, taken when the D − 1 thresholds
    sweep the table no more often than one binary search gathers from
    it (``keys.search_steps``); ``"search"`` otherwise."""
    if value_slots is not None and value_slots - 1 <= K.search_steps(t):
        return "runs"
    return "search"


def _delta_bounds(sm: SortedMode, values: jnp.ndarray, delta: float,
                  value_domain: Optional[jnp.ndarray]):
    """Per tuple, in original order: the [lo, hi) window of sorted
    positions whose value lies within δ of the tuple's own, inside its
    key segment.  A rank-coded lane over few values scans rank
    thresholds (:func:`_rank_threshold_bounds`); otherwise two binary
    searches find the bounds."""
    if sm.sorted_words is not None and sm.plan is not None \
            and sm.plan.with_values:
        plan, d = sm.plan, jnp.float32(delta)
        if plan.value_bits < 32 and delta_bounds_path(
                sm.seg_a.shape[0], value_domain.shape[0]) == "runs":
            lo, hi = _rank_threshold_bounds(sm, d, value_domain)
            return lo[sm.inv], hi[sm.inv]
        # packed path: δ-window bounds by *global* search over the sorted
        # key words — the query key carries the tuple's own subrelation
        # prefix with the value lane set to v∓δ and e_k at its extreme,
        # so the search self-clamps to the segment and no per-query
        # window (or segment_bounds scan) is needed.  -0.0 targets are
        # normalised so word order agrees with float order.
        t_lo, t_hi = sm.sorted_vals - d, sm.sorted_vals + d
        if plan.value_bits == 32:
            t_lo = jnp.where(t_lo == 0, jnp.float32(0.0), t_lo)
            t_hi = jnp.where(t_hi == 0, jnp.float32(0.0), t_hi)
            lane_lo = K.float_sort_bits(t_lo)
            lane_hi = K.float_sort_bits(t_hi)
        else:
            # rank-coded lane: the window bounds are domain ranks.  Every
            # value ≥ v-δ has rank ≥ searchsorted-left(v-δ); every value
            # ≤ v+δ has rank ≤ searchsorted-right(v+δ)-1 (≥ 0: the
            # tuple's own value is in the domain and ≤ v+δ).
            dom = value_domain.astype(jnp.float32)
            lane_lo = jnp.searchsorted(dom, t_lo,
                                       side="left").astype(jnp.uint32)
            lane_hi = (jnp.searchsorted(dom, t_hi, side="right")
                       - 1).astype(jnp.uint32)
        q_lo = plan.delta_query_words(sm.sorted_words, lane_lo)
        q_hi = plan.delta_query_words(sm.sorted_words, lane_hi)
        q_hi = q_hi[:-1] + (q_hi[-1] | jnp.uint32(plan.e_mask),)
        lo_idx = K.search_words(sm.sorted_words, q_lo, upper=False)[sm.inv]
        hi_idx = K.search_words(sm.sorted_words, q_hi, upper=True)[sm.inv]
    else:
        a = sm.seg_a[sm.inv]
        b = sm.seg_b[sm.inv]
        lo_idx = bsearch(sm.sorted_vals, a, b, values - jnp.float32(delta),
                         leq=False)
        hi_idx = bsearch(sm.sorted_vals, a, b, values + jnp.float32(delta),
                         leq=True)
    return lo_idx, hi_idx


def _rank_threshold_bounds(sm: SortedMode, d: jnp.ndarray,
                           value_domain: jnp.ndarray):
    """δ-window bounds per *sorted* position of a rank-coded lane over
    D domain values, by scans instead of searches.

    Inside a key segment the ranks never decrease, so the positions of
    rank ≥ t form a suffix of it, starting at ``A_t`` (the segment's end
    if none).  The window of a tuple of rank r is [A_lo(r), A_hi(r)),
    with lo(r) the least rank whose value is ≥ v_r − δ and hi(r) one
    past the greatest whose value is ≤ v_r + δ — the ranks the search
    path queries for, from the same float32 arithmetic, once per domain
    entry.  Splitting each segment where the rank first reaches t gives
    runs whose :func:`segment_bounds` hold ``A_t``: the run's start for
    a position of rank ≥ t, its end for one below.  ``A_0`` is the
    segment's start and ``A_D`` its end."""
    dom = value_domain.astype(jnp.float32)
    n, t = dom.shape[0], sm.seg_a.shape[0]
    lo_of = jnp.searchsorted(dom, dom - d, side="left",
                             method="compare_all").astype(jnp.int32)
    hi_of = jnp.searchsorted(dom, dom + d, side="right",
                             method="compare_all").astype(jnp.int32)
    rank = RX.extract_digit(sm.sorted_words, sm.plan.e_bits,
                            sm.plan.value_bits).astype(jnp.int32)
    # each position's threshold pair, selected over the D ranks (a rank
    # past the domain reads its last entry, as a clamped gather would)
    lo_t, hi_t = lo_of[n - 1], hi_of[n - 1]
    for r in range(n - 1):
        lo_t = jnp.where(rank == r, lo_of[r], lo_t)
        hi_t = jnp.where(rank == r, hi_of[r], hi_t)
    lo, hi = sm.seg_a, sm.seg_b
    if n > 1:
        ths = jnp.arange(1, n, dtype=jnp.int32)[:, None]
        prev = jnp.concatenate([rank[:1], rank[:-1]])
        seg_start = sm.seg_a == jnp.arange(t, dtype=jnp.int32)
        a, b = segment_bounds(seg_start | ((rank >= ths) & (prev < ths)))
        for i in range(n - 1):
            lo = jnp.where(lo_t == i + 1, a[i], lo)
            hi = jnp.where(hi_t == i + 1, b[i], hi)
    return lo, hi


# ---------------------------------------------------------------------------
# Stage 3: dedup + generating-tuple counts
# ---------------------------------------------------------------------------

def stage3_dedup(sig_lo: jnp.ndarray, sig_hi: jnp.ndarray,
                 tuple_first: jnp.ndarray, packed: bool = True,
                 sort_backend: str = "radix", use_pallas: bool = False):
    """Dedup clusters on their signatures with one sort; count *distinct*
    generating tuples per cluster (Alg. 6+7 reducer semantics).

    ``packed`` keys the sort on the (sig_lo, sig_hi) pair — the 2×32-bit
    cluster signature as one uint64 word, all 64 bits live for the
    radix backend (signatures are avalanched hashes); the lexsort
    branch is the bit-identical baseline kept for benchmarking.

    Returns (gen_count, is_unique) in original tuple order; ``is_unique``
    marks the first distinct generating tuple of each cluster."""
    t = sig_lo.shape[0]
    if packed:
        (s_lo, s_hi), (order,) = K.sort_with_payload(
            (sig_lo, sig_hi), (jnp.arange(t, dtype=jnp.int32),),
            backend=sort_backend, live_bits=64, use_pallas=use_pallas)
    else:
        order = lex_perm([sig_lo, sig_hi])
        s_lo, s_hi = sig_lo[order], sig_hi[order]
    s_first = tuple_first[order]
    cstart = segment_starts([s_lo, s_hi])
    a, b = segment_bounds(cstart)
    # distinct generating tuples per cluster: prefix-count differences at
    # the cluster window bounds (no scatter); a tuple is the cluster's
    # unique representative iff it is the window's first s_first entry.
    pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         cumulative(s_first.astype(jnp.int32), jax.lax.add)])
    pos = jnp.arange(t, dtype=jnp.int32)
    uniq_sorted = s_first & (pref[pos] == pref[a])
    # one inverse-permutation scatter + two gathers (scatters dominate
    # the non-sort cost of the pipeline on scatter-unfriendly backends)
    inv_order = jnp.zeros((t,), jnp.int32).at[order].set(pos)
    gen_of = (pref[b] - pref[a])[inv_order]
    is_unique = uniq_sorted[inv_order]
    return gen_of, is_unique


# ---------------------------------------------------------------------------
# The full pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PipelineResult:
    """Unified per-tuple mining output (original tuple order; length-T
    arrays), shared by every backend and variant."""
    sig_lo: jnp.ndarray        # cluster signature of the tuple's cluster
    sig_hi: jnp.ndarray
    is_unique: jnp.ndarray     # bool: first distinct generating tuple
    gen_count: jnp.ndarray     # distinct generating tuples of the cluster
    volume: jnp.ndarray        # float32 Π_k |component_k|
    density: jnp.ndarray       # Alg. 7 estimate  gen_count / volume
    keep: jnp.ndarray          # unique & density ≥ θ (& minsup)
    cardinalities: jnp.ndarray  # (N, T) distinct |component_k| per tuple
    range_lo: jnp.ndarray      # (N, T) component window starts (sorted ord.)
    range_hi: jnp.ndarray      # (N, T) window ends (exclusive)
    sorted_e: jnp.ndarray      # (N, T) per-mode entity columns, sorted order
    perms: jnp.ndarray         # (N, T) per-mode sort permutations

jax.tree_util.register_dataclass(
    PipelineResult,
    data_fields=["sig_lo", "sig_hi", "is_unique", "gen_count", "volume",
                 "density", "keep", "cardinalities", "range_lo", "range_hi",
                 "sorted_e", "perms"],
    meta_fields=[])


def density_of(gen_count: jnp.ndarray, volume: jnp.ndarray) -> jnp.ndarray:
    """Alg. 7 estimate ``gen_count / max(volume, 1)`` in float32 — the one
    expression of every path, evaluated on the device: a TPU's f32
    division is not IEEE-exact, so a host division differs in the last
    bits."""
    return gen_count.astype(jnp.float32) / jnp.maximum(volume, 1.0)


#: the top-level named scopes of ``mine_tuples``, in pipeline order
STAGE_SCOPES = ("stage1_sort", "stage2_components", "stage2_mix",
                "stage3_dedup")


def mine_tuples(tuples: jnp.ndarray, hash_lo: Sequence[jnp.ndarray],
                hash_hi: Sequence[jnp.ndarray], *,
                values: Optional[jnp.ndarray] = None,
                delta: Optional[float] = None, theta: float = 0.0,
                minsup: int = 0,
                perms: Optional[jnp.ndarray] = None,
                packed: Optional[bool] = None,
                sort_backend: Optional[str] = None,
                use_pallas: Optional[bool] = None,
                value_domain: Optional[jnp.ndarray] = None) -> PipelineResult:
    """The full three-stage pipeline on one shard (jit-able; T, N static).

    ``delta=None`` runs the prime cumulus operator (multimodal/OAC);
    otherwise the δ-range operator (NOAC) with ``theta`` acting as ρ_min
    and ``minsup`` as the per-mode minimal cardinality.  ``perms``
    (N, T) supplies precomputed per-mode sort orders (streaming).

    ``packed`` selects the single-word Stage-1/3 sort path (None: packed
    whenever the context's key fits 64 bits; False: always lexsort — the
    benchmarking baseline); ``sort_backend`` picks the word-sort
    algorithm ('radix' — the bit-plan-pruned LSD default — or 'lax';
    'lexsort' forces the column path like ``packed=False``).
    ``use_pallas`` routes the Stage-2 segment reductions (and the radix
    backend's histogram/rank sweeps) through the fused Pallas kernels
    (None: on TPU only).  ``value_domain`` — the sorted distinct values
    of the many-valued column, when the caller knows them — prunes the
    key's value lane to rank width (``core.keys``), shrinking the radix
    pass schedule; orderings are unchanged (rank coding is
    order-isomorphic), so all sort paths stay bit-identical.

    Every operation lies under one of the :data:`STAGE_SCOPES` named
    scopes (``delta_search`` nests inside ``stage2_components``), which
    the compiled program carries as each instruction's ``op_name`` and a
    profiler trace therefore attributes device time by."""
    t, n = tuples.shape
    if delta is not None and delta < 0:
        raise ValueError(f"delta must be >= 0, got {delta}")
    if use_pallas is None:
        use_pallas = kops.on_tpu()
    if values is None:
        value_domain = None
    plans = K.plan_context_keys(
        [h.shape[0] for h in hash_lo], with_values=values is not None,
        value_slots=(None if value_domain is None
                     else value_domain.shape[0]))
    backend = RX.resolve_sort_backend(sort_backend, packed, plans[0].fits)
    use_packed = backend != "lexsort"
    # the (sig_lo, sig_hi) pair always fits two words, so Stage 3 keeps
    # its packed sort even when the context's own key does not fit
    s3_backend = RX.resolve_sort_backend(sort_backend, packed, True)
    comps, sms = [], []
    for k in range(n):
        with jax.named_scope("stage1_sort"):
            sm = sort_mode(tuples, k, values=values,
                           perm=None if perms is None else perms[k],
                           plan=plans[k] if use_packed else None,
                           sort_backend=backend, use_pallas=use_pallas,
                           value_domain=value_domain)
        with jax.named_scope("stage2_components"):
            if delta is None:
                comps.append(prime_components(sm, hash_lo[k], hash_hi[k],
                                              use_pallas))
            else:
                comps.append(delta_components(sm, hash_lo[k], hash_hi[k],
                                              values, delta, use_pallas,
                                              value_domain=value_domain))
        sms.append(sm)
    with jax.named_scope("stage1_sort"):
        sorted_e = jnp.stack([sm.sorted_e for sm in sms])
        perms_out = jnp.stack([sm.perm.astype(jnp.int32) for sm in sms])
    with jax.named_scope("stage2_components"):
        cards = jnp.stack([c.card for c in comps])
        range_lo = jnp.stack([c.range_lo for c in comps])
        range_hi = jnp.stack([c.range_hi for c in comps])
    # Stage 2: per-tuple cluster = mix of per-mode component aggregates.
    with jax.named_scope("stage2_mix"):
        sig_lo, sig_hi = mix_signatures([c.sig_lo for c in comps],
                                        [c.sig_hi for c in comps])
        volume = jnp.ones((t,), jnp.float32)
        for c in comps:
            volume = volume * c.card.astype(jnp.float32)
    # Stage 3.  Mode 0's sort key covers the whole row, so its
    # first-of-run flags already mark the lowest-index copy of each
    # duplicate row (stable sorts) — no extra full-table sort needed;
    # gathering through mode 0's inverse permutation avoids a scatter.
    with jax.named_scope("stage3_dedup"):
        tfirst = sms[0].first_occ[sms[0].inv]
        gen_of, is_unique = stage3_dedup(sig_lo, sig_hi, tfirst,
                                         packed=s3_backend != "lexsort",
                                         sort_backend=s3_backend,
                                         use_pallas=use_pallas)
        density = density_of(gen_of, volume)
        keep = is_unique & (density >= jnp.float32(theta))
        if minsup:
            for c in comps:
                keep = keep & (c.card >= minsup)
    return PipelineResult(
        sig_lo, sig_hi, is_unique, gen_of, volume, density, keep,
        cardinalities=cards, range_lo=range_lo, range_hi=range_hi,
        sorted_e=sorted_e, perms=perms_out)


# ---------------------------------------------------------------------------
# Host-side materialisation (shared by all engines with component ranges)
# ---------------------------------------------------------------------------

def materialise(result: PipelineResult, only_kept: bool = True):
    """Extract cluster component sets [(components, density), ...] for kept
    (or all unique) tuples by slicing the per-mode sorted windows."""
    flag = np.asarray(result.keep if only_kept else result.is_unique)
    rlo, rhi = np.asarray(result.range_lo), np.asarray(result.range_hi)
    sorted_e = np.asarray(result.sorted_e)
    dens = np.asarray(result.density)
    n = sorted_e.shape[0]
    out = []
    for i in np.nonzero(flag)[0]:
        comps = []
        for k in range(n):
            window = sorted_e[k][rlo[k, i]:rhi[k, i]]
            comps.append(frozenset(np.unique(window).tolist()))
        out.append((tuple(comps), float(dens[i])))
    return out


def kept_sig_words(result) -> np.ndarray:
    """Sorted packed ``(sig_hi << 32) | sig_lo`` words of the kept
    clusters of one result — the per-snapshot signature *set* the
    serving layer diffs to find dirty clusters (``serve.clusters``
    packs identically; Stage 3 sorts the same word)."""
    keep = np.asarray(result.keep).astype(bool)
    m = np.uint64(0xFFFFFFFF)
    lo = np.asarray(result.sig_lo)[keep].astype(np.uint64) & m
    hi = np.asarray(result.sig_hi)[keep].astype(np.uint64) & m
    return np.unique((hi << np.uint64(32)) | lo)


def dirty_sig_count(prev: Optional[np.ndarray],
                    cur: np.ndarray) -> int:
    """Size of the symmetric difference of two sorted signature-word
    sets — how many clusters changed identity between two consecutive
    snapshots (the delta-index workload, surfaced by miners when
    ``track_dirty_sigs`` is on)."""
    if prev is None:
        return int(cur.size)
    inter = np.intersect1d(cur, prev, assume_unique=True).size
    return int(cur.size) + int(prev.size) - 2 * int(inter)


def _active_obs(obs):
    """The enabled observability hub or None — the pipeline's
    zero-overhead-when-disabled gate.  Duck-typed (``.enabled``,
    ``.metrics``, ``.tracer``): ``core`` imports only ``repro.obs``'s
    ``phase`` helper; callers pass a ``repro.obs.Obs`` (or nothing)."""
    return obs if (obs is not None
                   and getattr(obs, "enabled", False)) else None


def _copy_in(obs, tuples, values):
    """The table on the device: int32 tuples, float32 values (or None),
    in one ``mine.copy_in`` phase whose ``bytes`` is what is copied."""
    n = int(np.prod(np.shape(tuples))) + (
        0 if values is None else int(np.prod(np.shape(values))))
    with phase("mine.copy_in", obs, bytes=4 * n):
        tuples = jnp.asarray(tuples, jnp.int32)
        if values is not None:
            values = jnp.asarray(values, jnp.float32)
    return tuples, values


class PipelineMiner:
    """Base driver: jit-compiled single-shard pipeline over fixed sizes.

    Subclasses (``BatchMiner``, ``NOACMiner``) pin the component operator;
    everything else — hashing, jit caching, materialisation — is shared.

    Every mine marks its host phases as ``repro.*`` profiler spans
    (``obs.phase``): ``mine.value_domain`` (NOAC), ``mine.copy_in``,
    ``mine.dispatch``, ``mine.wait`` and, on the chunked and windowed
    paths, the host run sort ``stage1_sort``.  ``obs`` (an enabled
    ``repro.obs.Obs``) also times each phase into
    ``pipeline_stage_ms{stage}``, waits for the device inside the call
    and, on the windowed path, records per-window stage timings and
    memory peaks.  ``obs=None`` (the default) costs one TraceMe a
    phase."""

    def __init__(self, sizes: Sequence[int], *, theta: float = 0.0,
                 delta: Optional[float] = None, minsup: int = 0,
                 seed: int = 0x5EED, packed: Optional[bool] = None,
                 sort_backend: Optional[str] = None,
                 use_pallas: Optional[bool] = None,
                 prune_values: bool = True,
                 window_budget: Optional[int] = None,
                 obs=None):
        self.obs = obs
        self.sizes = tuple(int(s) for s in sizes)
        self.window_budget = (None if window_budget is None
                              else int(window_budget))
        self.theta = float(theta)
        self.delta = None if delta is None else float(delta)
        if self.delta is not None and self.delta < 0:
            # a negative δ makes the window [v-δ, v+δ] empty; the rank-
            # coded lane's searchsorted bounds would underflow instead
            raise ValueError(f"delta must be >= 0, got {self.delta}")
        self.minsup = int(minsup)
        self.packed = packed
        self.sort_backend = sort_backend
        self.use_pallas = use_pallas
        self.prune_values = bool(prune_values)
        self.key_plans = K.plan_context_keys(self.sizes,
                                             with_values=delta is not None)
        vecs = mode_hash_vectors(self.sizes, seed)
        self._lo = [jnp.asarray(lo) for lo, _ in vecs]
        self._hi = [jnp.asarray(hi) for _, hi in vecs]
        self._fn = jax.jit(functools.partial(
            mine_tuples, delta=self.delta, theta=self.theta,
            minsup=self.minsup, packed=packed, sort_backend=sort_backend,
            use_pallas=use_pallas))

    @property
    def resolved_sort_backend(self) -> str:
        """The actual Stage-1 sort path: 'radix' | 'lax' | 'lexsort'."""
        return RX.resolve_sort_backend(self.sort_backend, self.packed,
                                       self.key_plans[0].fits)

    @property
    def packed_active(self) -> bool:
        """True when Stage 1 runs the packed single-sort path."""
        return self.resolved_sort_backend != "lexsort"

    def value_domain(self, values) -> Optional[jnp.ndarray]:
        """Sorted distinct values for lane pruning (None when pruning is
        off or the caller forced the lexsort path — the shared
        ``radix.wants_value_pruning`` gate)."""
        if values is None or not RX.wants_value_pruning(
                self.prune_values, self.packed, self.sort_backend):
            return None
        return jnp.asarray(K.value_domain_host(values))

    def __call__(self, tuples, values=None) -> PipelineResult:
        obs = _active_obs(self.obs)
        vdom = None
        if self.delta is not None:
            if values is None:
                values = np.zeros((np.shape(tuples)[0],), np.float32)
            # domain from the caller's (usually host-side) array, before
            # the device transfer — np.unique never round-trips the
            # device column
            with phase("mine.value_domain", obs):
                vdom = self.value_domain(values)
        else:
            values = None
        tuples, values = _copy_in(obs, tuples, values)
        return self._dispatch(obs, tuples, values=values, value_domain=vdom)

    def _rank_lane_slots(self, value_domain) -> Optional[int]:
        """The domain size D when ``mine_tuples`` packs this mine's value
        lane rank-coded (a domain, and a packed sort of the pruned
        plans), else None — :func:`delta_bounds_path`'s argument."""
        if value_domain is None:
            return None
        slots = int(value_domain.shape[0])
        fits = K.plan_context_keys(self.sizes, True, slots)[0].fits
        if RX.resolve_sort_backend(self.sort_backend, self.packed,
                                   fits) == "lexsort":
            return None
        return slots

    def _dispatch(self, obs, tuples, **kw) -> PipelineResult:
        """Launch the jitted pipeline; with a hub, wait for it (and, on
        a NOAC mine, count each mode's δ-window path in
        ``pipeline_delta_bounds_total{path}``)."""
        if obs is not None and self.delta is not None:
            path = delta_bounds_path(
                tuples.shape[0],
                self._rank_lane_slots(kw.get("value_domain")))
            obs.metrics.counter("pipeline_delta_bounds_total",
                                path=path).inc(len(self.sizes))
        with phase("mine.dispatch", obs):
            res = self._fn(tuples, self._lo, self._hi, **kw)
        if obs is not None:
            # profiling forces the async dispatch to completion: the
            # measured figure is the real device wall time, and the
            # next stage's timer starts clean
            with phase("mine.wait", obs):
                jax.block_until_ready(res)
        return res

    def materialise(self, result: PipelineResult, tuples=None,
                    only_kept: bool = True):
        """``tuples`` is accepted for API compatibility and unused — the
        result carries its own component windows."""
        return materialise(result, only_kept)

    def mine_chunked(self, chunks, values=None,
                     chunk_budget: Optional[int] = None,
                     stats: Optional[dict] = None) -> PipelineResult:
        """Out-of-core chunked Stage 1 (DESIGN.md §4): build a host-side
        ``core.runs.RunStore`` chunk-by-chunk — each chunk sorted with
        O(chunk) working set, runs merged linearly — and feed the merged
        per-mode permutations to the jitted pipeline via ``perms``, so
        the device never sorts and the host never holds more than the
        row log plus one chunk's sort scratch.  Bit-identical to the
        in-core ``__call__`` on the same table (the store's host packers
        are the device packers, and stable merges preserve sort order).

        ``chunks`` is a single (T, N) table or an iterable of row
        chunks (``values`` aligned likewise for the δ variant);
        ``chunk_budget`` bounds rows-per-chunk, re-splitting anything
        larger.  A budget *smaller than the largest key segment* is
        fine — chunk runs merge stably, so a segment spanning many
        chunks reassembles exactly (``tests/test_window_property.py``
        regression-tests this); only degenerate budgets (< 1) raise.
        Valued tables get the constructor's last-write-wins
        canonicalisation (``core.runs``) — already-canonical contexts
        pass through unchanged.  Contexts whose key exceeds 64 bits
        fall back to one device sort of the assembled table."""
        from . import runs as RS
        if chunk_budget is not None and int(chunk_budget) < 1:
            raise ValueError(
                f"chunk_budget must be >= 1, got {chunk_budget}; pass "
                "None to ingest chunks as offered")
        obs = _active_obs(self.obs)
        # the host run sort IS Stage 1's sort on this path
        with phase("stage1_sort", obs):
            store = RS.RunStore(self.key_plans,
                                radix=self.resolved_sort_backend == "radix",
                                incremental=self.key_plans[0].fits,
                                stats=stats if stats is not None else {})
            for rows, vals in RS.iter_chunks(
                    chunks, values, chunk_budget,
                    with_values=self.delta is not None):
                store.add(rows, vals)
            store.prepare()
        if store.count == 0:
            raise ValueError("no data ingested")
        rows, vals = store.table()
        perms = store.perms()
        if perms is None:      # key exceeds 64 bits: no host runs
            # one device sort of the assembled table — with the same
            # value-lane pruning __call__ applies, so a key rescued by
            # the rank-coded lane still takes the packed path
            with phase("mine.value_domain", obs):
                kw = {"value_domain": self.value_domain(vals)}
        else:
            kw = {"perms": jnp.asarray(perms, jnp.int32)}
        targs, vargs = _copy_in(obs, rows, vals)
        return self._dispatch(obs, targs, values=vargs, **kw)

    def mine_windowed(self, chunks, values=None,
                      window_budget: Optional[int] = None,
                      stats: Optional[dict] = None,
                      probe=None) -> PipelineResult:
        """Fully windowed out-of-core mining (DESIGN.md §3c): the host
        run sort of :meth:`mine_chunked` *and* a device pipeline that
        streams Stage 1–3 through ``window_budget``-sized slices of
        the merged sorted order (``core.windowed``), so peak
        incremental device memory is O(window), not O(T).  The sort
        chunking and the device window loop share the one budget
        (``radix.plan_windows``).  Bit-identical to the in-core
        ``__call__`` on the same table; ``window_budget=None`` runs a
        single in-core window through the same code path.

        Raises for configurations the windowed path cannot honour
        bit-exactly (>64-bit keys, the forced-lexsort baseline) and
        for degenerate budgets — never a silent seam split."""
        from . import runs as RS
        from . import windowed as WD
        if window_budget is None:
            window_budget = self.window_budget
        if not self.key_plans[0].fits:
            raise ValueError(
                "mine_windowed needs 64-bit-packable keys; this "
                "context's key exceeds 64 bits — use mine_chunked")
        backend = self.resolved_sort_backend
        if backend == "lexsort":
            raise ValueError(
                "mine_windowed has no lexsort path (packed=False / "
                "sort_backend='lexsort'); use the monolithic pipeline "
                "for the lexsort baseline")
        if window_budget is not None and int(window_budget) < 1:
            raise ValueError(
                f"window_budget must be >= 1, got {window_budget}; "
                "pass None for a single in-core window")
        obs = _active_obs(self.obs)
        with phase("stage1_sort", obs):
            store = RS.RunStore(self.key_plans, radix=backend == "radix",
                                incremental=True,
                                stats=stats if stats is not None else {})
            for rows, vals in RS.iter_chunks(
                    chunks, values, window_budget,
                    with_values=self.delta is not None):
                store.add(rows, vals)
            store.prepare()
        if store.count == 0:
            raise ValueError("no data ingested")
        rows, vals = store.table()
        return WD.mine_windowed(
            rows, vals, store.perms(), plans=self.key_plans,
            hash_lo=self._lo, hash_hi=self._hi, delta=self.delta,
            theta=self.theta, minsup=self.minsup,
            window_budget=window_budget, sort_backend=backend,
            use_pallas=self.use_pallas, probe=probe, obs=obs)
