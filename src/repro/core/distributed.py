"""Distributed three-stage clustering (the paper's M/R algorithm mapped
onto a TPU mesh with ``shard_map``; DESIGN.md §3/§7).

Both the prime/multimodal variant and the many-valued NOAC variant
(δ/ρ_min/minsup) run here: the per-shard compute is the shared pipeline
of ``core.pipeline`` with the variant's component operator plugged in,
so the distribution strategy is written exactly once.

Tuples are block-partitioned (uniform by construction — this removes the
paper's hash-skew problem) over one or more mesh axes. Two merge
strategies, mirroring the centralise-vs-replicate discussion in the
paper's §1:

* ``replicate`` — all-gather the (small) tuple table over the data axes and
  let every shard run the batch pipeline on the full table, keeping only its
  own block's outputs. Communication: one all-gather of ``T×N`` int32 (plus
  ``T`` float32 values for NOAC); compute is duplicated ×P. This is the
  paper's "data replication" choice, executed as a log-depth ICI collective
  instead of HDFS replication-factor-3.

* ``shuffle`` — the faithful M/R shuffle. Stage 1 routes each tuple's
  ⟨subrelation, e_k[, value]⟩ record to the key's *owner shard* with a
  fixed-capacity ``all_to_all`` (MoE-dispatch pattern); owners
  sort/segment/hash their key ranges — running the variant's component
  operator (whole segment, or δ-range binary searches) — and answer with
  ⟨signature, cardinality⟩ per record (Stage 2 — 16 bytes instead of the
  paper's whole-cumulus shuffle). Stage 3 deduplicates and counts
  generating tuples on 8-byte cluster signatures gathered over the mesh.
  Skew shows up as capacity overflow and is *reported*, not silently
  dropped (a reducer-OOM analogue).

  When the context's sort key fits 64 bits (``core.keys``), senders ship
  the *pre-packed* key words (8 bytes/record instead of (N+1)×4) and
  owners sort the received words directly — entity ids and value columns
  are recovered from the key's bit-fields, so owners never re-pack or
  re-derive the shuffle key.  Wider keys fall back to the original
  column records behind the same API.

Both strategies return bit-identical signatures/densities to the
single-shard ``BatchMiner``/``NOACMiner`` (same hash vectors), which is
what the tests assert.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .._compat import cumulative
from ..obs.phases import phase
from . import keys as K
from . import pipeline as PL
from . import radix as RX
from . import runs as RS

Axis = tuple[str, ...]


@dataclasses.dataclass
class DistributedResult:
    """Global per-tuple outputs (sharded over the data axes)."""
    sig_lo: jnp.ndarray
    sig_hi: jnp.ndarray
    is_unique: jnp.ndarray
    gen_count: jnp.ndarray
    volume: jnp.ndarray
    density: jnp.ndarray
    keep: jnp.ndarray
    cardinalities: jnp.ndarray   # (N, T) distinct |component_k| per tuple
    n_clusters: jnp.ndarray      # scalar, replicated
    overflow: jnp.ndarray        # scalar: dropped records (0 == exact)

jax.tree_util.register_dataclass(
    DistributedResult,
    data_fields=["sig_lo", "sig_hi", "is_unique", "gen_count", "volume",
                 "density", "keep", "cardinalities", "n_clusters", "overflow"],
    meta_fields=[])


def _hash_columns(cols: Sequence[jnp.ndarray], salt: int) -> jnp.ndarray:
    """uint32 mix of int32 id columns (key → owner-shard hashing)."""
    h = jnp.full(cols[0].shape, jnp.uint32(salt))
    for c in cols:
        h = (h ^ c.astype(jnp.uint32)) * jnp.uint32(0x9E3779B1)
        h = h ^ (h >> 15)
    return h


def _range_partition(words, plan: K.ModeKeyPlan, axes, n_shards: int,
                     capacity: int, fallback_owner: jnp.ndarray):
    """Owner shard per record from the radix plan's *top-digit*
    histogram: the all-reduced 256-bucket histogram of the subrelation
    prefix's top 8 live bits — the same primitive the radix backend's
    sort is built on, here applied to the pre-shuffle keys — yields
    balanced contiguous key ranges (boundary of shard s at the digit
    where the cumulative count crosses s/n_shards of the total), so
    owners receive contiguous key ranges instead of hash-scattered
    ones.

    Two skew escapes fall back to ``fallback_owner`` (the hash
    partition, which spreads by the full subrelation key); both tests
    are all-reduced so every shard takes the same branch (a key's
    records must all reach one owner):

    * a single bucket exceeding a fair shard share (range cuts can only
      land on digit boundaries, so no contiguous assignment balances —
      e.g. power-law ids concentrating in top digit 0);
    * a source→owner *link* exceeding the dispatch ``capacity``: with
      shard-locally key-clustered data (e.g. block-sharded pre-sorted
      rows) a globally balanced range map still sends one shard's whole
      block to one owner, which hash partitioning never stresses."""
    # the digit may only read *subrelation* bits (above seg_shift) —
    # cutting below them would split a key segment across owners
    top_w = min(RX.HIST_DIGIT_BITS, plan.total_bits - plan.seg_shift)
    dig = RX.extract_digit(words, plan.total_bits - top_w, top_w)
    nb = 1 << top_w
    hist = jnp.zeros((nb,), jnp.int32).at[dig.astype(jnp.int32)].add(1)
    hist = jax.lax.psum(hist, axes)
    cum_before = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), cumulative(hist, jax.lax.add)[:-1]])
    total = jnp.maximum(cum_before[-1] + hist[-1], 1)
    # boundary math in float32: cum*n_shards overflows int32 at scale,
    # and any digit->shard function is correct (owners sort their own
    # ranges), so rounding at a boundary is harmless
    shard_of_digit = jnp.clip(
        (cum_before.astype(jnp.float32) * jnp.float32(n_shards)
         / total.astype(jnp.float32)).astype(jnp.int32),
        0, n_shards - 1)
    range_owner = shard_of_digit[dig.astype(jnp.int32)]
    local_link = jnp.zeros((n_shards,), jnp.int32).at[range_owner].add(1)
    link_max = jax.lax.pmax(local_link.max(), axes)
    skewed = ((hist.max() > total // jnp.int32(n_shards))
              | (link_max > jnp.int32(capacity)))
    return jnp.where(skewed, fallback_owner, range_owner)


# ---------------------------------------------------------------------------
# Shuffle strategy internals (per shard_map body)
# ---------------------------------------------------------------------------

def _dispatch(records: jnp.ndarray, owner: jnp.ndarray, n_shards: int,
              capacity: int):
    """Pack ``records`` (L, W) into a (n_shards*capacity, W) send buffer by
    owner shard, plus validity mask, slot handle per record and overflow.

    A record's rank in its owner's range is the number of earlier
    records with the same owner, read from per-owner running counts:
    one inclusive scan of the (n_shards, L) one-hot owner matrix, with
    no sort or search.  The owners' totals (the scan's last column)
    give which slots are filled, so validity needs no scatter."""
    shards = jnp.arange(n_shards, dtype=owner.dtype)
    hot = (owner[None, :] == shards[:, None]).astype(jnp.int32)
    count = cumulative(hot, jax.lax.add, axis=1)
    rank = (hot * count).sum(axis=0) - 1
    ok = rank < capacity
    nslots = n_shards * capacity
    # overflowed records go to a trash slot one past the end
    slot_safe = jnp.where(ok, owner * capacity + rank, nslots)
    buf = jnp.zeros((nslots + 1, records.shape[1]), records.dtype)
    buf = buf.at[slot_safe].set(records)[:nslots]
    # slot i is filled iff its place in its owner's range is below that
    # owner's total; on the flat index, since a reshape of an
    # (n_shards, capacity) mask costs a relayout
    i = jnp.arange(nslots, dtype=jnp.int32)
    valid = i % capacity < count[:, -1][i // capacity]
    overflow = (~ok).sum()
    return buf, valid, slot_safe, ok, overflow


def _owner_stage(recv: jnp.ndarray, rvalid: jnp.ndarray, n_other: int,
                 r_lo: jnp.ndarray, r_hi: jnp.ndarray,
                 delta: Optional[float], use_pallas: bool = False):
    """Owner-side Reduce-1 (column-record fallback): segment received
    ⟨key, e[, value]⟩ records and run the variant's component operator
    of ``core.pipeline`` (δ-windows by binary search inside each
    segment), producing per-record (set-signature, distinct
    cardinality, tuple-first flag)."""
    big = jnp.int32(np.iinfo(np.int32).max)
    key_cols = [jnp.where(rvalid, recv[:, j], big) for j in range(n_other)]
    e_col = jnp.where(rvalid, recv[:, n_other], big)
    l = recv.shape[0]
    if delta is not None:
        vals = jax.lax.bitcast_convert_type(recv[:, n_other + 1], jnp.float32)
        vals = jnp.where(rvalid, vals, jnp.float32(np.inf))
        perm = PL.lex_perm(key_cols + [vals, e_col])
    else:
        vals = None
        perm = PL.lex_perm(key_cols + [e_col])
    s_keys = [c[perm] for c in key_cols]
    s_e = e_col[perm]
    s_valid = rvalid[perm]
    s_vals = vals[perm] if vals is not None else None
    first_occ = PL.segment_starts(
        s_keys + ([s_vals] if s_vals is not None else []) + [s_e]) & s_valid
    seg_a, seg_b = PL.segment_bounds(PL.segment_starts(s_keys))
    inv = jnp.zeros((l,), jnp.int32).at[perm].set(
        jnp.arange(l, dtype=jnp.int32))
    sm = PL.SortedMode(perm, inv, seg_a, seg_b, jnp.where(s_valid, s_e, 0),
                       s_vals, first_occ)
    comps = (PL.prime_components(sm, r_lo, r_hi, use_pallas)
             if delta is None else
             PL.delta_components(sm, r_lo, r_hi, vals, delta, use_pallas))
    return comps.sig_lo, comps.sig_hi, comps.card, first_occ[inv]


def _validity_words(words, inval: jnp.ndarray, total_bits: int):
    """The key words with the validity flag folded in as one extra MSB
    (live bit ``total_bits``), so the owner sort runs as a single
    (total_bits+1)-bit radix instead of a variadic comparison sort."""
    if total_bits + 1 <= 32:
        return (words[-1] | (inval << total_bits),)
    hi = words[0] if len(words) == 2 else jnp.zeros_like(words[-1])
    return (hi | (inval << (total_bits - 32)), words[-1])


def _owner_stage_packed(recv: jnp.ndarray, rvalid: jnp.ndarray,
                        plan: K.ModeKeyPlan, r_lo: jnp.ndarray,
                        r_hi: jnp.ndarray, delta: Optional[float],
                        use_pallas: bool = False,
                        sort_backend: str = "radix",
                        value_domain=None):
    """Owner-side Reduce-1 over *pre-packed* key words: one stable sort
    keyed on (validity, key words) with the permutation carried as a
    payload, then the variant's component operator of ``core.pipeline``
    on the owner's ``SortedMode`` — δ-windows included, by the batch
    path's ``_delta_bounds``.  Entity ids and value columns are
    bit-field extractions from the shipped key, so owners never
    re-pack.

    The validity flag is folded into the key as its next bit (invalid
    slots sort last), so the sorted words stay globally ordered and the
    padding forms one segment of its own: no valid slot's segment, rank
    runs or search ever reaches it.  A key of exactly 64 bits leaves the
    flag no room; its sort carries the flag as a separate key and its
    δ-windows are searched inside each segment."""
    l = recv.shape[0]
    words = tuple(recv[:, i] for i in range(recv.shape[1]))
    inval = (~rvalid).astype(jnp.uint32)   # invalid slots sort last
    iota = jnp.arange(l, dtype=jnp.int32)
    values = None
    if plan.total_bits + 1 <= 64:
        ext = _validity_words(words, inval, plan.total_bits)
        if sort_backend == "radix":
            perm = RX.radix_sort_perm(ext, plan.total_bits + 1, use_pallas)
            s_words = tuple(w[perm] for w in ext)
        else:
            out = jax.lax.sort(ext + (iota,), num_keys=len(ext),
                               is_stable=True)
            s_words, perm = tuple(out[:-1]), out[-1]
        s_valid = RX.extract_digit(s_words, plan.total_bits, 1) == 0
        seg_words = K.drop_low_bits(s_words, plan.seg_shift)
        occ_words, sm_words, sm_plan = s_words, s_words, plan
    else:
        out = jax.lax.sort((inval,) + words + (iota,),
                           num_keys=1 + len(words), is_stable=True)
        s_inval, s_words, perm = out[0], tuple(out[1:-1]), out[-1]
        s_valid = s_inval == 0
        seg_words = (s_inval,) + K.drop_low_bits(s_words, plan.seg_shift)
        occ_words, sm_words, sm_plan = (s_inval,) + s_words, None, None
        if delta is not None:
            values = plan.extract_values(words, domain=value_domain)
    first_occ = PL.segment_starts(list(occ_words)) & s_valid
    seg_a, seg_b = PL.segment_bounds(PL.segment_starts(list(seg_words)))
    s_vals = (plan.extract_values(s_words, domain=value_domain)
              if delta is not None else None)
    inv = jnp.zeros((l,), jnp.int32).at[perm].set(iota)
    sm = PL.SortedMode(perm, inv, seg_a, seg_b,
                       jnp.where(s_valid, plan.extract_entity(s_words), 0),
                       s_vals, first_occ, sm_words, sm_plan)
    comps = (PL.prime_components(sm, r_lo, r_hi, use_pallas)
             if delta is None else
             PL.delta_components(sm, r_lo, r_hi, values, delta, use_pallas,
                                 value_domain=value_domain))
    return comps.sig_lo, comps.sig_hi, comps.card, first_occ[inv]


def _shuffle_mode(tuples, values, k, axes, n_shards, capacity, r_lo, r_hi,
                  delta, plan: Optional[K.ModeKeyPlan] = None,
                  use_pallas: bool = False, sort_backend: str = "radix",
                  value_domain=None):
    """Stages 1+2 of the M/R algorithm for one mode over ``axes``.

    With a fitting ``plan``, records on the wire are the packed key
    words (8 bytes each) and owners are key *ranges* balanced by the
    radix top-digit histogram; otherwise the original column records,
    hash-partitioned."""
    n = tuples.shape[1]
    with jax.named_scope("shuffle_route"):
        others = [tuples[:, j] for j in range(n) if j != k]
        hash_owner = (_hash_columns(others, 0xA11CE + k) %
                      jnp.uint32(n_shards)).astype(jnp.int32)
        if plan is not None and plan.fits:
            words = plan.pack_device(tuples, values, domain=value_domain)
            owner = (_range_partition(words, plan, axes, n_shards,
                                      capacity, hash_owner)
                     if sort_backend == "radix" else hash_owner)
            records = jnp.stack(words, axis=1)
        else:
            plan = None
            owner = hash_owner
            cols = others + [tuples[:, k]]
            if delta is not None:
                cols = cols + [jax.lax.bitcast_convert_type(values,
                                                            jnp.int32)]
            records = jnp.stack(cols, axis=1)
        buf, valid, slot, ok, overflow = _dispatch(records, owner, n_shards,
                                                   capacity)
    with jax.named_scope("shuffle_exchange"):
        recv = jax.lax.all_to_all(buf, axes, 0, 0, tiled=True)
        rvalid = jax.lax.all_to_all(valid.astype(jnp.int32), axes, 0, 0,
                                    tiled=True).astype(bool)
    with jax.named_scope("shuffle_owner"):
        if plan is not None:
            sig_lo, sig_hi, card, tfirst = _owner_stage_packed(
                recv, rvalid, plan, r_lo, r_hi, delta, use_pallas,
                sort_backend, value_domain)
        else:
            sig_lo, sig_hi, card, tfirst = _owner_stage(
                recv, rvalid, n - 1, r_lo, r_hi, delta, use_pallas)
        resp = jnp.stack([sig_lo, sig_hi, card.astype(jnp.uint32),
                          tfirst.astype(jnp.uint32)], axis=1)
    with jax.named_scope("shuffle_exchange"):
        resp = jax.lax.all_to_all(resp, axes, 0, 0, tiled=True)
        got = resp[slot]   # (L, 4) in original record order (garbage if !ok)
        return (got[:, 0], got[:, 1], got[:, 2].astype(jnp.int32),
                got[:, 3].astype(bool), ok, overflow)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class DistributedMiner:
    """Multi-device clustering over a mesh — prime *and* NOAC variants.

    Args:
      sizes: mode cardinalities.
      mesh: jax Mesh containing ``axes``.
      axes: data-parallel mesh axis name(s) the tuple table is sharded over.
      theta: minimal density threshold (paper Alg. 7 θ; prime variant).
      strategy: 'replicate' | 'shuffle'.
      capacity_factor: shuffle per-destination buffer slack (≥1).
      delta: many-valued δ — switches the engine to the NOAC variant.
      rho_min: NOAC minimal density (plays θ's role).
      minsup: NOAC minimal per-mode cardinality.
      packed: packed-key sort path (None: auto when the key fits 64 bits;
        False: column lexsort baseline).
      sort_backend: packed word-sort algorithm ('radix' default | 'lax';
        'lexsort' forces the column path).
      use_pallas: fused Pallas segment reductions (None: on TPU only).
      obs: an optional ``repro.obs.Obs`` hub, as ``PipelineMiner``
        takes: it times the host phases of ``__call__`` and counts
        ``pipeline_delta_bounds_total{path}`` and, on the shuffle,
        ``distributed_shuffle_records_total{mode}``,
        ``distributed_shuffle_slots_total{mode}`` and
        ``distributed_shuffle_retries_total``.
    """

    def __init__(self, sizes: Sequence[int], mesh, axes="data",
                 theta: float = 0.0, strategy: str = "replicate",
                 capacity_factor: float = 2.0, seed: int = 0x5EED,
                 max_retries: int = 4, delta: Optional[float] = None,
                 rho_min: float = 0.0, minsup: int = 0,
                 packed: Optional[bool] = None,
                 sort_backend: Optional[str] = None,
                 use_pallas: Optional[bool] = None,
                 prune_values: bool = True,
                 window_budget: Optional[int] = None,
                 obs=None):
        self.obs = obs
        self.sizes = tuple(int(s) for s in sizes)
        self.prune_values = bool(prune_values)
        #: shared streaming unit (DESIGN.md §3c): windows the incremental
        #: serving snapshot's device pipeline and rounds the shuffle's
        #: per-link dispatch capacity up to whole windows
        self.window_budget = (None if window_budget is None
                              else int(window_budget))
        self.mesh = mesh
        self.axes: Axis = (axes,) if isinstance(axes, str) else tuple(axes)
        self.delta = None if delta is None else float(delta)
        if self.delta is not None and self.delta < 0:
            raise ValueError(f"delta must be >= 0, got {self.delta}")
        self.theta = float(rho_min) if self.delta is not None else float(theta)
        self.minsup = int(minsup)
        self.strategy = strategy
        self.capacity_factor = float(capacity_factor)
        self.max_retries = int(max_retries)
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.packed = packed
        self.sort_backend = sort_backend
        self.key_plans = K.plan_context_keys(self.sizes,
                                             with_values=delta is not None)
        self.resolved_sort_backend = RX.resolve_sort_backend(
            sort_backend, packed, self.key_plans[0].fits)
        self.packed_active = self.resolved_sort_backend != "lexsort"
        from ..kernels import ops as kops
        self.use_pallas = (kops.on_tpu() if use_pallas is None
                           else bool(use_pallas))
        vecs = PL.mode_hash_vectors(self.sizes, seed)
        self._lo = [jnp.asarray(lo) for lo, _ in vecs]
        self._hi = [jnp.asarray(hi) for _, hi in vecs]
        if strategy not in ("replicate", "shuffle"):
            raise ValueError(strategy)
        self._fn = None
        self._t_global = None
        # incremental snapshot state (per-shard run stores, DESIGN.md §4)
        self._stores = None
        self._fn_perms = None
        self._t_perms = None
        #: None = auto (runs maintained whenever the key fits); False =
        #: log-only stores, every snapshot re-sorts on device (the
        #: benchmark baseline / memory-lean ingestion)
        self.stream_incremental: Optional[bool] = None
        self.stream_stats = {"snapshots": 0, "full_resorts": 0,
                             "merged_rows": 0, "chunk_sorted_rows": 0,
                             "tombstoned_rows": 0,
                             "incremental": self.key_plans[0].fits}
        # snapshot versioning (serve/service.py): mutating stream calls
        # bump ``stream_version``; snapshots record the version covered
        self.stream_version = 0
        self.snapshot_stream_version = 0
        # per-snapshot dirty-signature tracking (serve delta index);
        # off by default — it syncs the signature lanes to host.  Only
        # ``serving_snapshot`` notes sigs: it is the serving path, and
        # the only one whose result carries the full-table lanes.
        self.track_dirty_sigs = False
        self.last_kept_sigs: Optional[np.ndarray] = None
        self.last_dirty_sigs = 0
        # single-device serving pipeline (full PipelineResult with
        # component windows), compiled lazily per padded capacity
        self._serve_fn = None

    # -- shard bodies -------------------------------------------------------

    def _slice_block(self, res, tl):
        """This shard's block of a full-table ``PipelineResult`` as the
        ``DistributedResult`` both replicate bodies return."""
        shard_id = jax.lax.axis_index(self.axes)
        sl = jax.lax.dynamic_slice_in_dim
        start = shard_id * tl
        return DistributedResult(
            sig_lo=sl(res.sig_lo, start, tl),
            sig_hi=sl(res.sig_hi, start, tl),
            is_unique=sl(res.is_unique, start, tl),
            gen_count=sl(res.gen_count, start, tl),
            volume=sl(res.volume, start, tl),
            density=sl(res.density, start, tl),
            keep=sl(res.keep, start, tl),
            cardinalities=sl(res.cardinalities, start, tl, axis=1),
            n_clusters=res.is_unique.sum(),
            overflow=jnp.int32(0))

    def _body_replicate(self, tuples, values, vdom, lo, hi):
        axes = self.axes
        full = jax.lax.all_gather(tuples, axes, tiled=True)
        vfull = (jax.lax.all_gather(values, axes, tiled=True)
                 if self.delta is not None else None)
        res = PL.mine_tuples(full, lo, hi, values=vfull, delta=self.delta,
                             theta=self.theta, minsup=self.minsup,
                             packed=self.packed,
                             sort_backend=self.sort_backend,
                             use_pallas=self.use_pallas,
                             value_domain=vdom if vdom.shape[0] else None)
        return self._slice_block(res, tuples.shape[0])

    def _capacity(self, tl: int) -> int:
        """Per-link dispatch capacity of a shard of ``tl`` rows."""
        capacity = max(1, int(np.ceil(tl / self.n_shards
                                      * self.capacity_factor)))
        if self.window_budget:
            # per-link batches ship in whole windows of the shared plan
            # (capacity only sizes the dispatch buffers / overflow check,
            # so rounding up never changes a mined bit)
            wb = int(self.window_budget)
            capacity = -(-capacity // wb) * wb
        return capacity

    def _plans(self, value_slots: Optional[int]):
        """(key plans, sort backend) of a mine whose value lane is
        rank-coded over ``value_slots`` entries (None: the float lane).
        The backend resolves from the PRUNED plans: a key that only fits
        thanks to the rank-coded lane still takes the packed path."""
        plans = K.plan_context_keys(self.sizes,
                                    with_values=self.delta is not None,
                                    value_slots=value_slots)
        return plans, RX.resolve_sort_backend(self.sort_backend,
                                              self.packed, plans[0].fits)

    def _body_shuffle(self, tuples, values, vdom, lo, hi):
        axes, nsh = self.axes, self.n_shards
        tl, n = tuples.shape
        capacity = self._capacity(tl)
        # vdom is empty when pruning is off, restoring the 32-bit float
        # lane
        vdom_opt = vdom if vdom.shape[0] else None
        plans, backend = self._plans(
            None if vdom_opt is None else vdom_opt.shape[0])
        packed_active = backend != "lexsort"
        per_lo, per_hi, cards = [], [], []
        overflow = jnp.int32(0)
        tuple_first = None
        for k in range(n):
            slo, shi, card, tfirst, ok, ovf = _shuffle_mode(
                tuples, values, k, axes, nsh, capacity, lo[k], hi[k],
                self.delta,
                plan=plans[k] if packed_active else None,
                use_pallas=self.use_pallas,
                sort_backend=backend,
                value_domain=vdom_opt)
            per_lo.append(slo)
            per_hi.append(shi)
            cards.append(card)
            with jax.named_scope("shuffle_route"):
                overflow = overflow + ovf.astype(jnp.int32)
            if k == 0:
                tuple_first = tfirst
        with jax.named_scope("shuffle_route"):
            overflow = jax.lax.psum(overflow, axes)
        with jax.named_scope("stage2_mix"):
            sig_lo, sig_hi = PL.mix_signatures(per_lo, per_hi)
            volume = jnp.ones((tl,), jnp.float32)
            for c in cards:
                volume = volume * c.astype(jnp.float32)
            cardinalities = jnp.stack(cards)
        # Stage 3 on gathered signatures (12 bytes/tuple on the wire).
        with jax.named_scope("stage3_gather"):
            g_lo = jax.lax.all_gather(sig_lo, axes, tiled=True)
            g_hi = jax.lax.all_gather(sig_hi, axes, tiled=True)
            g_tf = jax.lax.all_gather(tuple_first, axes, tiled=True)
        with jax.named_scope("stage3_dedup"):
            s3_backend = RX.resolve_sort_backend(self.sort_backend,
                                                 self.packed, True)
            gen_of, is_unique = PL.stage3_dedup(
                g_lo, g_hi, g_tf, packed=s3_backend != "lexsort",
                sort_backend=s3_backend, use_pallas=self.use_pallas)
            shard_id = jax.lax.axis_index(axes)
            sl = jax.lax.dynamic_slice_in_dim
            start = shard_id * tl
            gen_l = sl(gen_of, start, tl)
            uniq_l = sl(is_unique, start, tl)
            density = PL.density_of(gen_l, volume)
            keep = uniq_l & (density >= jnp.float32(self.theta))
            if self.minsup:
                for c in cards:
                    keep = keep & (c >= self.minsup)
            n_clusters = is_unique.sum()
        return DistributedResult(
            sig_lo=sig_lo, sig_hi=sig_hi, is_unique=uniq_l, gen_count=gen_l,
            volume=volume, density=density, keep=keep,
            cardinalities=cardinalities, n_clusters=n_clusters,
            overflow=overflow)

    def _body_replicate_perms(self, tuples, values, perms, lo, hi):
        """Replicate-strategy body with *precomputed* global per-mode
        permutations (replicated input): the incremental snapshot path —
        Stage 1's sorts are skipped entirely, everything downstream is
        the stock pipeline."""
        axes = self.axes
        full = jax.lax.all_gather(tuples, axes, tiled=True)
        vfull = (jax.lax.all_gather(values, axes, tiled=True)
                 if self.delta is not None else None)
        res = PL.mine_tuples(full, lo, hi, values=vfull, delta=self.delta,
                             theta=self.theta, minsup=self.minsup,
                             perms=perms, packed=self.packed,
                             sort_backend=self.sort_backend,
                             use_pallas=self.use_pallas)
        return self._slice_block(res, tuples.shape[0])

    # -- public -------------------------------------------------------------

    def _out_specs(self):
        data_spec = P(self.axes)
        card_spec = P(None, self.axes)
        return DistributedResult(
            sig_lo=data_spec, sig_hi=data_spec, is_unique=data_spec,
            gen_count=data_spec, volume=data_spec, density=data_spec,
            keep=data_spec, cardinalities=card_spec, n_clusters=P(),
            overflow=P())

    def _build(self, t_global: int):
        body = (self._body_replicate if self.strategy == "replicate"
                else self._body_shuffle)
        fn = PL.shard_map(body, mesh=self.mesh,
                          in_specs=(P(self.axes, None), P(self.axes),
                                    P(), P(), P()),
                          out_specs=self._out_specs())
        return jax.jit(fn)

    def _build_perms(self):
        fn = PL.shard_map(self._body_replicate_perms, mesh=self.mesh,
                          in_specs=(P(self.axes, None), P(self.axes),
                                    P(), P(), P()),
                          out_specs=self._out_specs())
        return jax.jit(fn)

    def _coerce(self, tuples, values):
        tuples = jnp.asarray(tuples, jnp.int32)
        if values is None:
            values = jnp.zeros((tuples.shape[0],), jnp.float32)
        return tuples, jnp.asarray(values, jnp.float32)

    def _value_domain(self, values) -> jnp.ndarray:
        """Sorted distinct values for key-lane pruning, as a replicated
        array (empty = pruning off: prime variant, lexsort path, or
        ``prune_values=False``)."""
        if self.delta is None or not RX.wants_value_pruning(
                self.prune_values, self.packed, self.sort_backend):
            return jnp.zeros((0,), jnp.float32)
        return jnp.asarray(K.value_domain_host(values))

    def lowered(self, tuples, values=None):
        """Lower (no execution) for dry-run / roofline analysis of the
        mining pipeline itself — same artifact path as the LM cells."""
        tuples, values = self._coerce(tuples, values)
        vdom = self._value_domain(values)
        fn = self._build(tuples.shape[0])
        structs = (jax.ShapeDtypeStruct(tuples.shape, jnp.int32),
                   jax.ShapeDtypeStruct(values.shape, jnp.float32),
                   jax.ShapeDtypeStruct(vdom.shape, jnp.float32),
                   [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in self._lo],
                   [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in self._hi])
        with self.mesh:
            return fn.lower(*structs)

    def _bounds_path(self, t: int, vdom) -> str:
        """How this mine finds its δ-windows (``pipeline.delta_bounds_
        path``): over each owner's receive buffer on the shuffle, over
        the whole table on the replicate body."""
        slots = int(vdom.shape[0]) or None
        plans, backend = self._plans(slots)
        if slots is None or backend == "lexsort":
            return PL.delta_bounds_path(t, None)
        if self.strategy == "shuffle":
            if plans[0].total_bits + 1 > 64:
                return "search"     # no room for the validity bit
            t = self.n_shards * self._capacity(t // self.n_shards)
        return PL.delta_bounds_path(t, slots)

    def _launch(self, obs, tuples, values, vdom) -> DistributedResult:
        """Dispatch the jitted program; with a hub, count what it will
        do first (``DESIGN.md`` §11)."""
        t = tuples.shape[0]
        if obs is not None:
            m, n = obs.metrics, len(self.sizes)
            if self.delta is not None:
                m.counter("pipeline_delta_bounds_total",
                          path=self._bounds_path(t, vdom)).inc(n)
            if self.strategy == "shuffle":
                slots = self.n_shards * self.n_shards * self._capacity(
                    t // self.n_shards)
                for k in range(n):
                    m.counter("distributed_shuffle_records_total",
                              mode=str(k)).inc(t)
                    m.counter("distributed_shuffle_slots_total",
                              mode=str(k)).inc(slots)
        with phase("mine.dispatch", obs):
            return self._fn(tuples, values, vdom, self._lo, self._hi)

    def __call__(self, tuples, values=None) -> DistributedResult:
        """Run the pipeline. On shuffle-capacity overflow (the M/R skew
        failure mode the paper's §1 warns about) the capacity factor is
        doubled and the job re-executed — the analogue of Hadoop re-running
        a failed reducer with more memory.

        Host phases are ``repro.mine.*`` profiler spans under the batch
        path's names: ``mine.value_domain`` (NOAC), ``mine.copy_in``,
        ``mine.dispatch`` and, on the shuffle, ``mine.overflow_check``
        (the wait for the result's dropped-record count)."""
        obs = PL._active_obs(self.obs)
        t = int(np.shape(tuples)[0])
        if t % self.n_shards:
            raise ValueError(
                f"tuple count {t} not divisible by shard count "
                f"{self.n_shards}; pad with duplicated rows (idempotent)")
        if values is None:
            values = np.zeros((t,), np.float32)
        # the domain from the caller's (usually host-side) array, before
        # the copy-in: np.unique never round-trips the device column
        if self.delta is None:
            vdom = self._value_domain(values)       # empty
        else:
            with phase("mine.value_domain", obs):
                vdom = self._value_domain(values)
        tuples, values = PL._copy_in(obs, tuples, values)
        if self._fn is None or self._t_global != t:
            self._fn = self._build(t)
            self._t_global = t
        res = self._launch(obs, tuples, values, vdom)
        retries = 0
        while self.strategy == "shuffle":
            with phase("mine.overflow_check", obs):
                dropped = int(res.overflow)
            if dropped == 0:
                break
            if retries == self.max_retries:
                # overflowed records were dropped by _dispatch —
                # returning would hand back silently-wrong clusters
                raise RuntimeError(
                    f"shuffle capacity overflow persists after "
                    f"{self.max_retries} retries (capacity_factor="
                    f"{self.capacity_factor}); the partition is too "
                    f"skewed for n_shards={self.n_shards}")
            retries += 1
            if obs is not None:
                obs.metrics.counter("distributed_shuffle_retries_total").inc()
            self.capacity_factor *= 2.0
            self._fn = self._build(t)
            res = self._launch(obs, tuples, values, vdom)
        return res

    # -- incremental snapshots (per-shard run stores, DESIGN.md §4) ---------

    def reset_stream(self) -> None:
        """Drop all ingested stream state (per-shard stores)."""
        self._stores = None
        for k in ("snapshots", "full_resorts", "merged_rows",
                  "chunk_sorted_rows", "tombstoned_rows"):
            self.stream_stats[k] = 0

    def _ensure_stores(self):
        if self._stores is None:
            inc = self.key_plans[0].fits and self.stream_incremental \
                is not False
            radix = self.resolved_sort_backend == "radix"
            n = self.n_shards if inc else 1
            self._stores = [RS.RunStore(self.key_plans, radix=radix,
                                        incremental=inc,
                                        stats=self.stream_stats)
                            for _ in range(n)]
        return self._stores

    def _route(self, rows: np.ndarray) -> np.ndarray:
        stores = self._ensure_stores()
        if len(stores) == 1:
            return np.zeros(rows.shape[0], np.int64)
        return RS.shard_of_rows(rows, stores[0]._identity_plan(),
                                len(stores))

    def _scatter(self, op: str, rows, values=None) -> None:
        """Route rows to their owner shard's store by the fixed
        radix-range partition of the entity-only identity key — the
        host-side analogue of the shuffle's range partitioner — and
        apply ``op`` per shard."""
        rows = np.atleast_2d(np.asarray(rows, np.int32))
        if rows.shape[0] == 0:
            return
        vals = None
        if self.delta is not None and op != "delete":
            vals = (np.zeros(rows.shape[0], np.float32) if values is None
                    else np.asarray(values, np.float32))
        stores = self._ensure_stores()
        owner = self._route(rows)
        for s, store in enumerate(stores):
            sel = np.nonzero(owner == s)[0]
            if sel.size == 0:
                continue
            sub_vals = None if vals is None else vals[sel]
            if op == "delete":
                store.delete(rows[sel])
            else:
                getattr(store, op)(rows[sel], sub_vals)
        self.stream_version += 1

    def ingest(self, rows, values=None) -> None:
        """Stream a chunk into the per-shard run stores (valued streams
        upsert — last write wins, like the batch constructor)."""
        self._scatter("add", rows, values)

    def upsert(self, rows, values=None) -> None:
        self._scatter("upsert", rows, values)

    def delete(self, rows) -> None:
        self._scatter("delete", rows)

    @property
    def stream_count(self) -> int:
        """Live (non-tombstoned) rows across all shard stores."""
        if not self._stores:
            return 0
        return sum(s.count - s.dead for s in self._stores)

    def _gathered(self, with_run: bool):
        """Concatenated survivor tables + (incremental path) the
        globally merged run: shard runs offset into the concatenated
        table and merged linearly — mode 0 concatenates outright, its
        shard key ranges are disjoint by the range routing."""
        stores = [s for s in self._stores if s.count]
        rows = np.concatenate([s.table()[0] for s in stores])
        vals = (np.concatenate([s.table()[1] for s in stores])
                if self.delta is not None else None)
        run, off = None, 0
        if with_run:
            for s in stores:
                r = RS.offset_run(s.runs[0], off)
                if run is None:
                    run = r
                else:
                    run = RS.merge_runs(run, r)
                    self.stream_stats["merged_rows"] += run.size
                off += s.count
        return rows, vals, run

    def snapshot(self, full_remine: bool = False) -> DistributedResult:
        """Mine the current stream exactly.  The incremental path folds
        each shard's runs (linear merges of only what changed), merges
        the per-shard runs into global permutations, and runs the
        replicate body with Stage 1's sorts skipped; ``full_remine=True``
        (or a non-fitting key) is the re-sort-every-shard baseline —
        the padded table through the one-shot ``__call__`` path."""
        if self._stores is None:
            raise RS.NoDataError("no data ingested")
        self.snapshot_stream_version = self.stream_version
        incremental = (not full_remine
                       and all(s.incremental for s in self._stores))
        if incremental and self.strategy == "shuffle":
            # the merged-perms body replicates the full table per shard
            # (all_gather) — running it would silently break the memory
            # bound the shuffle strategy was chosen for
            raise ValueError(
                "incremental snapshots run the replicate-with-perms "
                "body; strategy='shuffle' mining is one-shot only — "
                "use snapshot(full_remine=True) or strategy='replicate'")
        self.stream_stats["snapshots"] += 1
        for s in self._stores:
            s.prepare() if incremental else s.compact()
        if self.stream_count == 0:
            raise ValueError("no live rows (everything deleted)")
        rows, vals, run = self._gathered(with_run=incremental)
        count = rows.shape[0]
        cap = RS.snapshot_cap(count, self.n_shards)
        rows, vals = RS.padded_table(rows, vals, cap)
        if not incremental or run is None:
            self.stream_stats["full_resorts"] += 1
            return self(rows, vals)
        perms = RS.padded_perms(run, self.key_plans, rows[:1],
                                None if vals is None else vals[:1],
                                count, cap)
        tuples, values = self._coerce(rows, vals)
        if self._fn_perms is None or self._t_perms != cap:
            self._fn_perms = self._build_perms()
            self._t_perms = cap
        return self._fn_perms(tuples, values,
                              jnp.asarray(perms, jnp.int32),
                              self._lo, self._hi)

    def serving_snapshot(self,
                         full_remine: bool = False) -> PL.PipelineResult:
        """Serving twin of :meth:`snapshot`: a *full-table*
        ``PipelineResult`` — component windows included, which
        ``DistributedResult`` deliberately drops — so a
        ``serve.clusters.ClusterIndex`` can be built straight from a
        distributed stream.  Runs the single-device pipeline on the
        gathered survivor table; on the incremental path the per-shard
        runs are folded and merged into global permutations exactly as
        :meth:`snapshot` does, so Stage 1 never re-sorts here either.
        Signatures are bit-identical to :meth:`snapshot` / the batch
        miner (same hash vectors)."""
        if self._stores is None:
            raise RS.NoDataError("no data ingested")
        self.snapshot_stream_version = self.stream_version
        incremental = (not full_remine
                       and all(s.incremental for s in self._stores))
        self.stream_stats["snapshots"] += 1
        for s in self._stores:
            s.prepare() if incremental else s.compact()
        if self.stream_count == 0:
            raise ValueError("no live rows (everything deleted)")
        rows, vals, run = self._gathered(with_run=incremental)
        count = rows.shape[0]
        cap = RS.snapshot_cap(count)
        rows, vals = RS.padded_table(rows, vals, cap)
        targs = jnp.asarray(rows, jnp.int32)
        vargs = None if vals is None else jnp.asarray(vals, jnp.float32)
        if self._serve_fn is None:
            self._serve_fn = jax.jit(functools.partial(
                PL.mine_tuples, delta=self.delta, theta=self.theta,
                minsup=self.minsup, packed=self.packed,
                sort_backend=self.sort_backend,
                use_pallas=self.use_pallas))
        if not incremental or run is None:
            self.stream_stats["full_resorts"] += 1
            # same value-lane pruning the one-shot __call__ applies (the
            # perms path below stays domain-free like snapshot()'s — the
            # store's merged runs carry the unpruned float lane)
            vdom = self._value_domain(vals) if vals is not None else None
            if vdom is not None and not vdom.shape[0]:
                vdom = None
            res = self._serve_fn(targs, self._lo, self._hi, values=vargs,
                                 value_domain=vdom)
        else:
            perms = RS.padded_perms(run, self.key_plans, rows[:1],
                                    None if vals is None else vals[:1],
                                    count, cap)
            if self.window_budget and self.packed_active:
                # windowed serving remine (DESIGN.md §3c): the merged
                # global perms feed the bounded device window loop —
                # bit-identical to the monolithic perms call below
                from . import windowed as WD
                res = WD.mine_windowed(
                    rows, vals, perms, plans=self.key_plans,
                    hash_lo=self._lo, hash_hi=self._hi, delta=self.delta,
                    theta=self.theta, minsup=self.minsup,
                    window_budget=self.window_budget,
                    sort_backend=self.resolved_sort_backend,
                    use_pallas=self.use_pallas)
            else:
                res = self._serve_fn(targs, self._lo, self._hi,
                                     values=vargs,
                                     perms=jnp.asarray(perms, jnp.int32))
        if self.track_dirty_sigs:
            sigs = PL.kept_sig_words(res)
            self.last_dirty_sigs = PL.dirty_sig_count(
                self.last_kept_sigs, sigs)
            self.last_kept_sigs = sigs
        return res


def pad_tuples(tuples: np.ndarray, multiple: int) -> np.ndarray:
    """Pad the tuple table to a multiple by repeating the first row — the
    mining algebra is duplicate-idempotent (paper §5.1 / K3 argument)."""
    t = tuples.shape[0]
    pad = (-t) % multiple
    if pad == 0:
        return tuples
    return np.concatenate([tuples, np.repeat(tuples[:1], pad, 0)], 0)


def pad_values(values: np.ndarray, multiple: int) -> np.ndarray:
    """Value-column companion of ``pad_tuples`` (pads with the first value,
    keeping V a function of the tuple)."""
    t = values.shape[0]
    pad = (-t) % multiple
    if pad == 0:
        return values
    return np.concatenate([values, np.repeat(values[:1], pad, 0)], 0)
