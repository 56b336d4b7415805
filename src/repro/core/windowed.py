"""Windowed device pipeline: Stage 1–3 through bounded HBM key windows
(DESIGN.md §3c).

The monolithic ``pipeline.mine_tuples`` materialises every Stage-1/2/3
intermediate at full table length T on the device, so a single
accelerator can only mine tables that fit in device memory.  This
module streams the *same* three stages through ``window_budget``-sized
slices of the merged sorted order (the ``RunStore`` per-mode host
permutations are the window iterator), carrying the open segment's
seam state across windows, and is leaf-for-leaf bit-identical to the
monolithic path:

* **Stage 1** — per mode, the device scans ``budget``-row slices of
  the sorted packed key words through the fused segment reduction
  (``kernels/segment_reduce`` via ``kops.segment_reduce``, exactly
  what ``pipeline.masked_prefix`` runs).  The seam carry is three
  scalars — the running masked prefix sums (hash-lane lo/hi, distinct
  counter) — plus the previous window's last key word(s): uint32/int32
  addition is associative, so adding the carried last inclusive value
  to the next window's local scan reproduces the global prefix sums
  *exactly*, no matter how many windows a single key segment (or NOAC
  δ-window) spans.  The host assembles the exclusive (T+1) prefix
  arrays and derives segment bounds / δ-window bounds from the sorted
  uint64 keys it already holds (``pack_host`` ≡ ``pack_device``
  bit-for-bit, and ``np.searchsorted`` over the packed uint64 keys is
  ``keys.search_words`` by construction).

* **Stage 2** — the signature mix and volume product are elementwise,
  so they run as ``budget``-sized device maps over original tuple
  order, reusing ``pipeline.mix_signatures`` verbatim.

* **Stage 3** — each original-order window is sorted on the packed
  2×32-bit cluster signature on device (``keys.sort_with_payload``,
  the same Stage-3 sort the monolithic path runs), then a host-side
  k-way combine merges the per-window runs keyed on the packed
  signature word — the same two-searchsorted stable merge as
  ``runs.merge_runs``, earlier windows on the a-side, so the combined
  order equals the monolithic stable sort's (sig, original position)
  order.  Group stats (distinct generating tuples, uniqueness) are the
  monolithic prefix-difference formulas on the combined order.

Memory model: the device holds O(window) stage buffers plus the O(n_k)
hash vectors; the host holds the O(T) table, sorted keys and result
arrays — which it must hold anyway (the table comes from the host run
store, and results are consumed host-side).  Peak *incremental* device
memory is O(window), not O(T); ``benchmarks/packed.py`` gates this
with a live-allocation probe (``core.memprobe``).

The window plan (``radix.plan_windows``) is shared with the host run
sort (``RunStore`` ``chunk_budget``) and the distributed shuffle's
per-link batch capacity — one streaming unit end to end.

Results are returned as **host (numpy) arrays** inside the usual
``PipelineResult``: shipping the O(T) result back to the device would
reintroduce the O(T) device footprint the windowing exists to avoid,
and every consumer (``materialise``, serving snapshots, tests) reads
results through ``np.asarray`` anyway.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..obs.phases import phase
from . import keys as K
from . import pipeline as P
from . import radix as RX

#: Stage names reported through the ``probe`` callback (one call per
#: device window dispatch, after the transfer back blocks).
STAGES = ("stage1_scan", "stage2_mix", "stage3_sort")

_U64_FULL = 0xFFFFFFFFFFFFFFFF


# ---------------------------------------------------------------------------
# Jitted window bodies (cached per static configuration; jax re-traces
# per window shape, which is constant = the budget)
# ---------------------------------------------------------------------------

_FN_CACHE: dict = {}


def _scan_fn(nwords: int, e_mask: int, use_pallas: bool):
    """Stage-1 window body: first-occurrence flags from the key words
    (seam-aware via ``first0``), fused masked segment reduction, carry
    addition.  Returns the window's inclusive global prefix sums and
    the new carry (its last elements — window padding repeats the last
    real key, so pads have ``first=False`` and contribute nothing)."""
    key = ("scan", nwords, e_mask, use_pallas)
    if key not in _FN_CACHE:
        def f(words, first0, c_lo, c_hi, c_cnt, r_lo, r_hi):
            flag = words[0][1:] != words[0][:-1]
            for w in words[1:]:
                flag = flag | (w[1:] != w[:-1])
            first = jnp.concatenate([first0[None], flag])
            e = (words[-1] & jnp.uint32(e_mask)).astype(jnp.int32)
            lo, hi, cnt = kops.segment_reduce(r_lo[e], r_hi[e], first,
                                              use_pallas=use_pallas)
            lo = lo + c_lo
            hi = hi + c_hi
            cnt = cnt + c_cnt
            return lo, hi, cnt, lo[-1], hi[-1], cnt[-1]
        _FN_CACHE[key] = jax.jit(f)
    return _FN_CACHE[key]


def _mix_fn(n_modes: int):
    """Stage-2 window body: ``pipeline.mix_signatures`` + the volume
    product over (N, B) per-mode stacks — elementwise, so windows are
    trivially independent."""
    key = ("mix", n_modes)
    if key not in _FN_CACHE:
        def f(slo, shi, card):
            lo, hi = P.mix_signatures([slo[k] for k in range(n_modes)],
                                      [shi[k] for k in range(n_modes)])
            vol = jnp.ones(slo.shape[1:], jnp.float32)
            for k in range(n_modes):
                vol = vol * card[k].astype(jnp.float32)
            return lo, hi, vol
        _FN_CACHE[key] = jax.jit(f)
    return _FN_CACHE[key]


def _s3_fn(backend: str, use_pallas: bool):
    """Stage-3 window body: one stable device sort of the window's
    packed signatures with an iota payload (the monolithic Stage-3
    sort at window size)."""
    key = ("s3", backend, use_pallas)
    if key not in _FN_CACHE:
        def f(sig_lo, sig_hi):
            t = sig_lo.shape[0]
            (s_lo, s_hi), (idx,) = K.sort_with_payload(
                (sig_lo, sig_hi), (jnp.arange(t, dtype=jnp.int32),),
                backend=backend, live_bits=64, use_pallas=use_pallas)
            return s_lo, s_hi, idx
        _FN_CACHE[key] = jax.jit(f)
    return _FN_CACHE[key]


def _density_fn():
    """Density window body: ``pipeline.density_of``."""
    if "density" not in _FN_CACHE:
        _FN_CACHE["density"] = jax.jit(P.density_of)
    return _FN_CACHE["density"]


# ---------------------------------------------------------------------------
# Host helpers (numpy mirrors of the pipeline's segment primitives)
# ---------------------------------------------------------------------------

def _split_words(keys_u64: np.ndarray, nwords: int) -> Tuple[np.ndarray, ...]:
    """Host uint64 keys -> the device's msb-first uint32 word tuple."""
    lo = (keys_u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    if nwords == 1:
        return (lo,)
    return ((keys_u64 >> np.uint64(32)).astype(np.uint32), lo)


def _diff_flags(sorted_keys: np.ndarray) -> np.ndarray:
    """Host ``segment_starts`` over one sorted uint64 key column."""
    f = np.empty(sorted_keys.shape[0], bool)
    f[0] = True
    np.not_equal(sorted_keys[1:], sorted_keys[:-1], out=f[1:])
    return f


def _seg_bounds(flags: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host ``pipeline.segment_bounds``: forward cummax / reverse
    cummin over start flags -> per-position [a, b) segment windows."""
    t = flags.shape[0]
    pos = np.arange(t, dtype=np.int32)
    a = np.maximum.accumulate(np.where(flags, pos, 0)).astype(np.int32)
    suff = np.minimum.accumulate(
        np.where(flags, pos, np.int32(t))[::-1])[::-1]
    b = np.concatenate([suff[1:], np.full(1, t, np.int32)]).astype(np.int32)
    return a, b


def _scatter(perm: np.ndarray, sorted_arr: np.ndarray) -> np.ndarray:
    """Sorted-order array -> original tuple order (the inverse-perm
    gather of the monolithic path, as one scatter)."""
    out = np.empty(sorted_arr.shape[0], sorted_arr.dtype)
    out[perm] = sorted_arr
    return out


def _pad_tail(arr: np.ndarray, budget: int, fill=None) -> np.ndarray:
    """Pad a tail window to the full budget (constant window shapes ->
    one jit trace per stage).  ``fill=None`` repeats the last element
    (Stage 1: equal keys keep ``first=False`` on the pads)."""
    short = budget - arr.shape[0]
    if short <= 0:
        return arr
    pad = np.full(short, arr[-1] if fill is None else fill, arr.dtype)
    return np.concatenate([arr, pad])


def _merge_pair(a, b):
    """Stable two-searchsorted merge of two (sig_word, orig_idx) runs,
    a-side winning ties — ``runs.merge_runs`` on signature words."""
    ka, ia = a
    kb, ib = b
    if ka.size == 0:
        return b
    if kb.size == 0:
        return a
    if ka[-1] <= kb[0]:
        return np.concatenate([ka, kb]), np.concatenate([ia, ib])
    if kb[-1] < ka[0]:
        return np.concatenate([kb, ka]), np.concatenate([ib, ia])
    pa = np.searchsorted(kb, ka, side="left") + np.arange(ka.size)
    pb = np.searchsorted(ka, kb, side="right") + np.arange(kb.size)
    mk = np.empty(ka.size + kb.size, np.uint64)
    mi = np.empty(ka.size + kb.size, np.int64)
    mk[pa] = ka
    mk[pb] = kb
    mi[pa] = ia
    mi[pb] = ib
    return mk, mi


def _kway_combine(parts):
    """Balanced k-way combine of per-window signature runs.  Adjacent
    pairs merge with the left (earlier windows, smaller original
    indices) on the a-side, so ties resolve to ascending original
    position — the stable global Stage-3 order."""
    parts = list(parts)
    while len(parts) > 1:
        parts = [parts[i] if i + 1 == len(parts)
                 else _merge_pair(parts[i], parts[i + 1])
                 for i in range(0, len(parts), 2)]
    return parts[0]


# ---------------------------------------------------------------------------
# The windowed driver
# ---------------------------------------------------------------------------

def _window_phase(stage: str, prof):
    """One device window of ``stage``: a ``repro.window.<stage>`` span,
    timed into ``pipeline_window_ms{stage}`` when ``prof`` is a hub."""
    return phase(f"window.{stage}", prof, metric="pipeline_window_ms",
                 stage=stage)


def mine_windowed(rows, values, perms, *,
                  plans: Sequence[K.ModeKeyPlan],
                  hash_lo, hash_hi,
                  delta: Optional[float] = None, theta: float = 0.0,
                  minsup: int = 0,
                  window_budget: Optional[int] = None,
                  sort_backend: str = "radix",
                  use_pallas: Optional[bool] = None,
                  probe: Optional[Callable[[str], None]] = None,
                  obs=None) -> P.PipelineResult:
    """Mine ``rows`` through bounded device windows; bit-identical to
    ``pipeline.mine_tuples`` on the same table (every ``PipelineResult``
    leaf, permutations included).

    ``rows``/``values`` is the host table, ``perms`` the (N, T) merged
    per-mode sort permutations (``RunStore.perms``).  ``plans`` must be
    the *un-pruned* context key plans (float value lane — the same
    plans the run store packed with); ``hash_lo``/``hash_hi`` the
    per-mode hash vectors.  ``window_budget=None`` runs a single
    in-core window through the same code path.

    ``probe`` (optional) is called with a :data:`STAGES` name after
    each device window dispatch completes — the peak-memory
    instrumentation hook of ``benchmarks/packed.py``.

    ``obs`` (an *enabled* ``repro.obs.Obs``, duck-typed) turns on
    per-stage profiling: per-window and per-stage wall-time
    histograms, the seam-carry count (windows entered mid-segment),
    and — when no ``probe`` is supplied — a ``core.memprobe`` peak
    sample per stage, all folded into the hub's registry plus one
    ``pipeline.windowed`` span.  Each device window of each stage is a
    ``repro.window.<stage>`` profiler span either way; ``obs=None``
    adds nothing else to the loop.

    Raises ``ValueError`` for degenerate budgets (< 1) and for
    configurations the windowed path cannot honour bit-exactly
    (non-fitting >64-bit keys, the forced-lexsort baseline, rank-coded
    value lanes) instead of silently widening or splitting — the loud
    twin of the seam-carry contract (DESIGN.md §3c).
    """
    if not plans[0].fits:
        raise ValueError(
            "windowed mining needs 64-bit-packable keys (plans[0].fits); "
            "this context's key exceeds 64 bits — use mine_chunked or the "
            "monolithic lexsort path instead")
    if sort_backend not in ("radix", "lax"):
        raise ValueError(
            f"windowed mining supports sort_backend 'radix' or 'lax', got "
            f"{sort_backend!r}; the lexsort baseline has no packed host "
            "keys to window over")
    if use_pallas is None:
        use_pallas = kops.on_tpu()
    rows = np.asarray(rows, np.int32)
    t, n = rows.shape
    if delta is not None:
        if delta < 0:
            raise ValueError(f"delta must be >= 0, got {delta}")
        if values is None:
            values = np.zeros((t,), np.float32)
        values = np.asarray(values, np.float32)
        if not plans[0].with_values or plans[0].value_bits != 32:
            raise ValueError(
                "windowed mining needs the un-pruned float value lane "
                "(plan_context_keys(..., value_slots=None))")
    else:
        values = None
    perms = np.asarray(perms)
    if perms.shape != (n, t):
        raise ValueError(f"perms shape {perms.shape} != {(n, t)}")
    wplan = RX.plan_windows(t, window_budget)   # raises on budget < 1
    budget = wplan.budget

    prof = obs if (obs is not None
                   and getattr(obs, "enabled", False)) else None
    mp = None
    if prof is not None:
        if probe is None:
            from . import memprobe as MP
            mp = MP.MemProbe()
            probe = mp
        stage_ms = {st: 0.0 for st in STAGES}
        seam_carries = 0
        sp = prof.tracer.start("pipeline.windowed", rows=t, modes=n,
                               budget=budget, windows=len(wplan.bounds))

    hash_lo = [jnp.asarray(h) for h in hash_lo]
    hash_hi = [jnp.asarray(h) for h in hash_hi]

    # ---- Stage 1: per-mode windowed masked-prefix scans + host bounds
    mode_sig_lo = np.empty((n, t), np.uint32)
    mode_sig_hi = np.empty((n, t), np.uint32)
    mode_card = np.empty((n, t), np.int32)
    mode_rlo = np.empty((n, t), np.int32)
    mode_rhi = np.empty((n, t), np.int32)
    sorted_e = np.empty((n, t), np.int32)
    tfirst = None
    for k in range(n):
        plan = plans[k]
        perm = perms[k].astype(np.int64)
        sk = plan.pack_host(rows, values)[perm]
        scan = _scan_fn(plan.words, plan.e_mask, use_pallas)
        pref_lo = np.zeros(t + 1, np.uint32)
        pref_hi = np.zeros(t + 1, np.uint32)
        pref_cnt = np.zeros(t + 1, np.int32)
        c_lo, c_hi, c_cnt = (jnp.uint32(0), jnp.uint32(0), jnp.int32(0))
        for w0, w1 in wplan.bounds:
            with _window_phase("stage1_scan", prof) as ph:
                win = _pad_tail(sk[w0:w1], budget)
                words = tuple(jnp.asarray(w) for w in
                              _split_words(win, plan.words))
                first0 = bool(w0 == 0 or sk[w0] != sk[w0 - 1])
                f0 = jnp.asarray(first0)
                lo, hi, cnt, c_lo, c_hi, c_cnt = scan(
                    words, f0, c_lo, c_hi, c_cnt, hash_lo[k], hash_hi[k])
                pref_lo[w0 + 1:w1 + 1] = np.asarray(lo)[:w1 - w0]
                pref_hi[w0 + 1:w1 + 1] = np.asarray(hi)[:w1 - w0]
                pref_cnt[w0 + 1:w1 + 1] = np.asarray(cnt)[:w1 - w0]
                if probe is not None:
                    probe("stage1_scan")
            if prof is not None:
                if not first0:      # entered mid-segment: a seam carry
                    seam_carries += 1
                stage_ms["stage1_scan"] += ph.ms
        # component windows in sorted order: whole key segment (prime)
        # or the δ-value range inside it (NOAC, global self-clamping
        # search — the host twin of keys.search_words)
        if delta is None:
            a, b = _seg_bounds(_diff_flags(sk >> np.uint64(plan.seg_shift)))
        else:
            d = np.float32(delta)
            s_vals = values[perm]
            t_lo = (s_vals - d).astype(np.float32)
            t_hi = (s_vals + d).astype(np.float32)
            t_lo = np.where(t_lo == 0, np.float32(0.0), t_lo)
            t_hi = np.where(t_hi == 0, np.float32(0.0), t_hi)
            lane_lo = K.float_sort_bits_host(t_lo).astype(np.uint64)
            lane_hi = K.float_sort_bits_host(t_hi).astype(np.uint64)
            base = sk & np.uint64(~((1 << plan.seg_shift) - 1) & _U64_FULL)
            eb = np.uint64(plan.e_bits)
            q_lo = base | (lane_lo << eb)
            q_hi = base | (lane_hi << eb) | np.uint64(plan.e_mask)
            a = np.searchsorted(sk, q_lo, side="left").astype(np.int32)
            b = np.searchsorted(sk, q_hi, side="right").astype(np.int32)
        bl, al = b.astype(np.int64), a.astype(np.int64)
        mode_sig_lo[k] = _scatter(perm, pref_lo[bl] - pref_lo[al])
        mode_sig_hi[k] = _scatter(perm, pref_hi[bl] - pref_hi[al])
        mode_card[k] = _scatter(perm, pref_cnt[bl] - pref_cnt[al])
        mode_rlo[k] = _scatter(perm, a)
        mode_rhi[k] = _scatter(perm, b)
        sorted_e[k] = rows[perm, k]
        if k == 0:
            # mode 0's key covers the whole row: its first-occurrence
            # flags mark the lowest-index copy of each duplicate row
            tfirst = _scatter(perm, _diff_flags(sk))

    # ---- Stage 2: elementwise mix/volume windows over original order
    mixfn = _mix_fn(n)
    sig_lo = np.empty(t, np.uint32)
    sig_hi = np.empty(t, np.uint32)
    volume = np.empty(t, np.float32)
    for w0, w1 in wplan.bounds:
        with _window_phase("stage2_mix", prof) as ph:
            wl = w1 - w0
            pad = budget - wl
            slo = np.pad(mode_sig_lo[:, w0:w1], ((0, 0), (0, pad)))
            shi = np.pad(mode_sig_hi[:, w0:w1], ((0, 0), (0, pad)))
            scd = np.pad(mode_card[:, w0:w1], ((0, 0), (0, pad)))
            lo, hi, vol = mixfn(jnp.asarray(slo), jnp.asarray(shi),
                                jnp.asarray(scd))
            sig_lo[w0:w1] = np.asarray(lo)[:wl]
            sig_hi[w0:w1] = np.asarray(hi)[:wl]
            volume[w0:w1] = np.asarray(vol)[:wl]
            if probe is not None:
                probe("stage2_mix")
        if prof is not None:
            stage_ms["stage2_mix"] += ph.ms

    # ---- Stage 3: per-window device signature sorts + host combine
    s3fn = _s3_fn(sort_backend, use_pallas)
    parts = []
    for w0, w1 in wplan.bounds:
        with _window_phase("stage3_sort", prof) as ph:
            wl = w1 - w0
            s_lo, s_hi, idx = s3fn(
                jnp.asarray(_pad_tail(sig_lo[w0:w1], budget, fill=0)),
                jnp.asarray(_pad_tail(sig_hi[w0:w1], budget, fill=0)))
            s_lo, s_hi = np.asarray(s_lo), np.asarray(s_hi)
            idx = np.asarray(idx)
            # drop tail pads: a stable sort's real-element subsequence
            # is exactly the stable sort of the real elements alone
            m = idx < wl
            # the Stage-3 sort keys (sig_lo, sig_hi) msb-first — sig_lo
            # is the high word of the packed signature the combine
            # merges on
            word = ((s_lo[m].astype(np.uint64) << np.uint64(32))
                    | s_hi[m].astype(np.uint64))
            parts.append((word, (w0 + idx[m]).astype(np.int64)))
            if probe is not None:
                probe("stage3_sort")
        if prof is not None:
            stage_ms["stage3_sort"] += ph.ms
    s_word, order = _kway_combine(parts)
    # group stats on the combined order — the monolithic stage3_dedup
    # prefix-difference formulas on host
    s_first = tfirst[order]
    a3, b3 = _seg_bounds(_diff_flags(s_word))
    pref = np.concatenate([np.zeros(1, np.int32),
                           np.cumsum(s_first.astype(np.int32),
                                     dtype=np.int32)])
    pos = np.arange(t, dtype=np.int32)
    uniq_sorted = s_first & (pref[pos] == pref[a3])
    gen_sorted = pref[b3.astype(np.int64)] - pref[a3.astype(np.int64)]
    gen_count = np.empty(t, np.int32)
    gen_count[order] = gen_sorted
    is_unique = np.empty(t, bool)
    is_unique[order] = uniq_sorted

    # density per window on the device, where the monolithic path
    # divides (its f32 division may round differently from the host's)
    dfn = _density_fn()
    density = np.empty(t, np.float32)
    for w0, w1 in wplan.bounds:
        density[w0:w1] = np.asarray(dfn(
            jnp.asarray(_pad_tail(gen_count[w0:w1], budget, fill=0)),
            jnp.asarray(_pad_tail(volume[w0:w1], budget, fill=0))))[:w1 - w0]
    keep = is_unique & (density >= np.float32(theta))
    if minsup:
        for k in range(n):
            keep = keep & (mode_card[k] >= minsup)
    if prof is not None:
        m = prof.metrics
        for st in STAGES:
            m.histogram("pipeline_stage_ms", stage=st).observe(
                stage_ms[st])
            sp.set(f"{st}_ms", stage_ms[st])
        m.counter("pipeline_seam_carries_total").inc(seam_carries)
        m.gauge("pipeline_windows").set(len(wplan.bounds))
        m.gauge("pipeline_window_budget").set(budget)
        if mp is not None:
            for st, peak in mp.report()["stages"].items():
                m.gauge("pipeline_window_peak_bytes", stage=st).set(peak)
            sp.set("peak_bytes", mp.peak_bytes)
            sp.set("peak_bytes_source", mp.source)
        sp.set("seam_carries", seam_carries)
        sp.finish()
    return P.PipelineResult(
        sig_lo=sig_lo, sig_hi=sig_hi, is_unique=is_unique,
        gen_count=gen_count, volume=volume, density=density, keep=keep,
        cardinalities=mode_card, range_lo=mode_rlo, range_hi=mode_rhi,
        sorted_e=sorted_e, perms=perms.astype(np.int32))
