"""Bit-plan-pruned LSD radix sort: the default backend of
``keys.sort_with_payload`` (DESIGN.md §3b).

The packed keys of ``core.keys`` are fixed-width words whose *live* bit
count is known statically from the bit-width plans, which makes an LSD
radix sort strictly cheaper than a comparison sort: only digits that
overlap live bit ranges get a pass, so a 28-bit movielens key is two
passes and a 60-bit NOAC key four — never a function of the 64-bit
container.

Two device formulations, both producing the *same stable permutation*
as ``lax.sort`` bit-for-bit (what ``tests/test_radix_property.py``
asserts):

* **Composite-word passes** (default off-TPU).  The measured CPU cost
  model (MEMORY: cpu-perf-cost-model) shows XLA-CPU's *variadic* sort —
  any ``lax.sort`` carrying a payload operand — runs ~16x slower than
  its single-array fast path (~100 ms vs ~6 ms at T=120k), and every
  scatter costs ~9-15 ms.  So each pass sorts ONE uint32 word
  ``(digit << pos_bits) | position``: the embedded position makes the
  word unique (stability for free) and *is* the back-pointer, so the
  pass permutation comes out of the sorted word's low bits — histogram,
  rank and scatter all disappear.  The digit width is the complement of
  the position bits (``32 - ceil(log2 T)``, 15 bits at T=120k), which
  also minimises the pass count.

* **Histogram ranks** (``use_pallas``, auto-enabled on TPU like
  ``segment_reduce``).  The classic 8-bit-digit formulation: one sweep
  over the words builds the per-pass histograms for *every* pruned
  digit position (``kernels/radix_sort.radix_histogram`` — the same
  top-digit histogram primitive the distributed shuffle runs on its
  pre-shuffle keys as a range partitioner), then each pass ranks
  elements as ``bucket_start[digit]
  + running occurrence`` with a chained-carry one-sweep kernel
  (``radix_rank``) and applies the rank with one scatter.

``lax.sort`` remains available behind the same API (``backend='lax'``),
and contexts whose key exceeds 64 bits keep the N+1-column lexsort path
exactly as before — the selector only ever touches fitting packed keys.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the histogram formulation's digit and the shared bit-field reader live
# with its kernels, which import nothing from ``core``
from .._compat import cumulative
from ..kernels.radix_sort import HIST_DIGIT_BITS, extract_digit

#: Valid values of the ``sort_backend`` selector threaded through the
#: engines.  ``None``/'auto' resolve to 'radix' for fitting keys.
SORT_BACKENDS = ("radix", "lax", "lexsort")


def pos_bits(t: int) -> int:
    """Bits needed to embed positions 0..t-1 in a composite word."""
    return max(1, int(np.ceil(np.log2(max(int(t), 2)))))


@dataclasses.dataclass(frozen=True)
class RadixPlan:
    """Static pass schedule for sorting ``live_bits``-wide keys of a
    length-``t`` array: ``shifts[p]``/``widths[p]`` give pass p's digit
    as a bit range of the conceptual ≤64-bit key (LSB first)."""
    t: int
    live_bits: int
    pos_bits: int
    shifts: Tuple[int, ...]
    widths: Tuple[int, ...]

    @property
    def passes(self) -> int:
        return len(self.shifts)


def plan_radix(live_bits: int, t: int,
               digit_bits: Optional[int] = None) -> RadixPlan:
    """Pass schedule covering exactly the live bits (bit-plan pruning):
    ``ceil(live_bits / digit_bits)`` passes, digit width defaulting to
    the composite-word maximum ``32 - pos_bits(t)``."""
    live_bits = max(1, int(live_bits))
    pb = pos_bits(t)
    w = int(digit_bits) if digit_bits else 32 - pb
    if not 0 < w < 32:
        raise ValueError(f"digit width {w} out of range")
    shifts, widths, s = [], [], 0
    while s < live_bits:
        shifts.append(s)
        widths.append(min(w, live_bits - s))
        s += w
    return RadixPlan(int(t), live_bits, pb, tuple(shifts), tuple(widths))


# ---------------------------------------------------------------------------
# Device sort
# ---------------------------------------------------------------------------

def _perm_composite(words, plan: RadixPlan) -> jnp.ndarray:
    """Stable sort permutation via composite-word passes (no payload
    operands, no scatters — see module docstring)."""
    t = plan.t
    iota = jnp.arange(t, dtype=jnp.uint32)
    pmask = jnp.uint32((1 << plan.pos_bits) - 1)
    perm = None
    for shift, width in zip(plan.shifts, plan.widths):
        dig = extract_digit(words, shift, width)
        if perm is not None:
            dig = dig[perm]
        (s,) = jax.lax.sort(((dig << plan.pos_bits) | iota,), num_keys=1,
                            is_stable=False)
        src = (s & pmask).astype(jnp.int32)
        perm = src if perm is None else perm[src]
    return perm


def _perm_histogram(words, plan: RadixPlan, use_pallas: bool) -> jnp.ndarray:
    """Stable sort permutation via histogram ranks over ``plan``'s digit
    schedule (the ``kernels/radix_sort`` pair; one rank scatter per
    pass).  The plan must use ≤``HIST_DIGIT_BITS``-wide digits."""
    from ..kernels import ops as kops
    hists = kops.radix_histogram(words, plan.shifts, plan.widths,
                                 use_pallas=use_pallas)
    t = plan.t
    iota = jnp.arange(t, dtype=jnp.int32)
    perm = None
    for p, (shift, width) in enumerate(zip(plan.shifts, plan.widths)):
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             cumulative(hists[p], jax.lax.add)[:-1]])
        dig = extract_digit(words, shift, width)
        if perm is not None:
            dig = dig[perm]
        rank = kops.radix_rank(dig, starts, use_pallas=use_pallas)
        src = jnp.zeros((t,), jnp.int32).at[rank].set(iota)
        perm = src if perm is None else perm[src]
    return perm


def radix_sort_perm(words: Sequence[jnp.ndarray], live_bits: int,
                    use_pallas: bool = False,
                    max_passes: Optional[int] = None) -> jnp.ndarray:
    """Permutation stably sorting msb-first packed ``words`` ascending,
    bit-identical to ``lax.sort`` with an iota payload.

    ``max_passes`` truncates the LSD schedule (benchmark per-pass
    attribution only — a truncated sort is *not* a total order); it
    counts passes of the formulation actually run (composite-word
    digits, or 8-bit histogram digits under ``use_pallas``)."""
    plan = plan_radix(live_bits, words[0].shape[0],
                      digit_bits=HIST_DIGIT_BITS if use_pallas else None)
    if max_passes is not None:
        plan = dataclasses.replace(plan, shifts=plan.shifts[:max_passes],
                                   widths=plan.widths[:max_passes])
    if use_pallas:
        return _perm_histogram(words, plan, use_pallas)
    return _perm_composite(words, plan)


def sort_with_payload_radix(words: Sequence[jnp.ndarray],
                            payloads: Sequence[jnp.ndarray],
                            live_bits: int, use_pallas: bool = False):
    """Drop-in for ``keys.sort_with_payload``: same (sorted_words,
    sorted_payloads) tuples, stability included, via the radix
    permutation + gathers instead of carrying payload sort operands."""
    perm = radix_sort_perm(words, live_bits, use_pallas)
    return (tuple(w[perm] for w in words),
            tuple(p[perm] for p in payloads))


# ---------------------------------------------------------------------------
# Host sort (streaming chunk runs)
# ---------------------------------------------------------------------------

def radix_argsort_host(keys: np.ndarray, live_bits: int) -> np.ndarray:
    """Stable ascending argsort of uint64 packed keys, LSD over 16-bit
    digits — numpy's stable sort is a radix sort for ≤16-bit integers,
    so each pass rides that fast path instead of a 64-bit mergesort.
    Bit-identical to ``np.argsort(keys, kind='stable')``."""
    keys = np.ascontiguousarray(keys, np.uint64)
    order = np.arange(keys.shape[0], dtype=np.int64)
    cur = keys
    shift = 0
    live_bits = max(1, int(live_bits))
    while shift < live_bits:
        w = min(16, live_bits - shift)
        dig = ((cur >> np.uint64(shift))
               & np.uint64((1 << w) - 1)).astype(np.uint16)
        o = np.argsort(dig, kind="stable")
        order = order[o]
        cur = cur[o]
        shift += w
    return order


# ---------------------------------------------------------------------------
# Window plan (shared sort/reduce streaming unit)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WindowPlan:
    """Static schedule of contiguous ``[start, stop)`` slices covering a
    length-``t`` sorted order in ``budget``-row windows.  This is the
    *one* streaming unit of the out-of-core path (DESIGN.md §3c): the
    host run sort chunks on it (``RunStore`` ``chunk_budget``), the
    device Stage-1/2/3 window loop iterates it, and the distributed
    shuffle rounds its per-link capacity up to a multiple of it — the
    same way the radix histogram sweep's block grid tiles one pass."""
    t: int
    budget: int

    @property
    def n_windows(self) -> int:
        return -(-self.t // self.budget)

    @property
    def bounds(self) -> Tuple[Tuple[int, int], ...]:
        return tuple((lo, min(lo + self.budget, self.t))
                     for lo in range(0, self.t, self.budget))


def plan_windows(t: int, budget: Optional[int] = None) -> WindowPlan:
    """Build the shared window plan.  ``budget=None`` (or >= t) is a
    single in-core window.  Degenerate budgets raise instead of being
    silently clamped: a silently-widened or silently-split window is
    exactly the failure mode the seam-carry contract exists to rule
    out, so misuse must be loud."""
    t = int(t)
    if t < 1:
        raise ValueError(f"window plan needs a non-empty table, got t={t}")
    if budget is None:
        return WindowPlan(t, t)
    budget = int(budget)
    if budget < 1:
        raise ValueError(
            f"window_budget must be >= 1, got {budget}; pass None for a "
            "single in-core window")
    return WindowPlan(t, min(budget, t))


# ---------------------------------------------------------------------------
# Backend resolution (single source of truth for every engine)
# ---------------------------------------------------------------------------

def resolve_sort_backend(sort_backend: Optional[str],
                         packed: Optional[bool], fits: bool) -> str:
    """Map the user-facing (sort_backend, packed) pair onto the actual
    Stage-1/3 sort path: 'radix' (default for fitting keys), 'lax' (the
    packed comparison-sort baseline) or 'lexsort' (column fallback —
    forced, or required because the key exceeds 64 bits)."""
    if sort_backend not in (None, "auto") + SORT_BACKENDS:
        raise ValueError(
            f"sort_backend={sort_backend!r}; valid: {SORT_BACKENDS}")
    if sort_backend == "lexsort" or packed is False or not fits:
        return "lexsort"
    if sort_backend in (None, "auto"):
        return "radix"
    return sort_backend


def wants_value_pruning(prune_values, packed, sort_backend) -> bool:
    """Single definition of "should this engine compute the lane-pruning
    value domain?" — pruning is off only when disabled or when the
    caller forced the lexsort path.  Deliberately independent of the
    un-pruned ``fits``: a key that overflows 64 bits only because of
    the 32-bit float lane packs fine once pruned, so the sort path is
    re-resolved from the pruned plans afterwards."""
    return (bool(prune_values) and packed is not False
            and sort_backend != "lexsort")
