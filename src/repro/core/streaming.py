"""Online / streaming clustering (paper §2 online setting) with
merge-based incremental snapshots and upsert (tombstone) streams.

The paper's online Algorithm 1 keeps dictionaries and appends pointers
per incoming triple.  The accelerator analogue keeps, per mode, the
tuple table's *sorted order* as a set of sorted runs — the shared
``core.runs.RunStore`` storage layer (DESIGN.md §4), which this engine
drives against the shared pipeline of ``core.pipeline``:

* ``add(chunk)`` sorts **only the chunk** (O(c log c) per mode) into a
  new run; geometric compaction merges runs linearly, so every tuple is
  merged O(log T) times over the stream's lifetime.
* ``upsert(rows, values)`` / ``delete(rows)`` tombstone superseded
  versions in the store — last-write-wins, exactly the batch
  constructor's canonicalisation (``core.context``) — which lifts the
  historical precondition that valued streams be per-tuple
  value-consistent: a valued ``add`` *is* an upsert.
* ``snapshot()`` compacts tombstones away, k-way-merges the surviving
  runs into full per-mode permutations (linear in T, no re-sort) and
  hands them to the jitted pipeline via its ``perms`` argument, which
  skips Stage 1's sorts and recomputes segments/signatures/dedup from
  the pre-sorted order.

This cuts the amortised per-snapshot cost of Stage 1 — the dominant
term of the one-pass pipeline — from O(T log T) re-sorting to
O(chunk log T) merging; Stage 3's signature dedup still sorts the
(8-byte) signature array on device.  Snapshots are *exact*: identical
cluster sets (and bit-identical signatures) to a full re-mine of the
survivor table, which is what the tests assert.  Both variants stream:
prime/multimodal (θ) and NOAC (δ/ρ_min/minsup).

The store merges host-packed uint64 keys from the *same* ``core.keys``
bit-width plans the device pipeline sorts by, so host-merged
permutations and device sorts order identically by construction.  The
streaming plans keep the un-pruned float value lane (runs must stay
mergeable when later chunks introduce unseen values).  If a context's
key does not fit in 64 bits, the engine transparently falls back to
exact full re-sorting per snapshot and reports it in
``stats['incremental']``; upsert/delete still work (tombstones live in
the log, not the runs).

Properties kept from the paper's online algorithm:
* one pass over the data (each tuple enters the log once),
* per-chunk latency O(c log c + merge debt) with O(log T) total
  recompilations (power-of-two padding),
* checkpointable: ``state.checkpoint()`` serialises the run arrays and
  tombstones themselves, so restore is O(T) array loads — no re-sort
  (legacy buffer-only blobs still restore via one lazy rebuild sort).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from . import pipeline as P
from . import runs as RS

#: Checkpoint/restore entry point (kept under its historical name; the
#: state object *is* the shared run store).
StreamState = RS.RunStore


class StreamingMiner(P.PipelineMiner):
    """Online one-pass mining with exact snapshot-on-demand semantics.

    Ingestion: ``add`` (append; valued streams upsert — see module
    docstring), ``upsert`` (insert-or-replace by tuple, last write
    wins), ``delete`` (tombstone).  ``snapshot()`` mines the current
    survivor set exactly."""

    def __init__(self, sizes, theta: float = 0.0, seed: int = 0x5EED,
                 delta: Optional[float] = None, rho_min: float = 0.0,
                 minsup: int = 0, incremental: bool = True,
                 packed: Optional[bool] = None,
                 sort_backend: Optional[str] = None,
                 use_pallas: Optional[bool] = None,
                 prune_values: bool = True,
                 window_budget: Optional[int] = None):
        # prune_values is accepted for registry-kwarg uniformity but has
        # no effect on snapshots: the streaming device pipeline shares
        # the host store's un-pruned float value lane (see module
        # docstring) — only a direct PipelineMiner.__call__ would prune.
        super().__init__(sizes, theta=(rho_min if delta is not None
                                       else theta),
                         delta=delta, minsup=minsup, seed=seed,
                         packed=packed, sort_backend=sort_backend,
                         use_pallas=use_pallas, prune_values=prune_values,
                         window_budget=window_budget)
        # host packing shares the device pipeline's bit-width plans
        # (core.keys) — the packers are bit-identical by construction
        self._codecs = self.key_plans
        self.incremental = bool(incremental) and all(c.fits
                                                     for c in self._codecs)
        self.state: Optional[RS.RunStore] = None
        self.stats = {"snapshots": 0, "full_resorts": 0, "merged_rows": 0,
                      "chunk_sorted_rows": 0, "tombstoned_rows": 0,
                      "incremental": self.incremental}
        # snapshot versioning (serve/service.py): every mutating call
        # bumps ``stream_version``; ``snapshot()`` records the version it
        # covers, so a published snapshot can be tagged with exactly the
        # writes it reflects
        self.stream_version = 0
        self.snapshot_stream_version = 0
        # per-snapshot dirty-signature tracking (serve delta index):
        # off by default — it forces a host transfer of the signature
        # lanes inside snapshot(), which mining benchmarks must not pay
        self.track_dirty_sigs = False
        self.last_kept_sigs: Optional[np.ndarray] = None
        self.last_dirty_sigs = 0
        # kept for API compatibility: the snapshot materialiser
        self.miner = self

    # -- ingestion ----------------------------------------------------------

    def _store(self) -> RS.RunStore:
        """The run store, created on first use and re-adopted after a
        checkpoint restore (a restored store may lack plans — legacy
        blobs — or carry its own stats dict)."""
        if self.state is None:
            self.state = RS.RunStore(
                self._codecs, radix=self.resolved_sort_backend == "radix",
                incremental=self.incremental, stats=self.stats)
        s = self.state
        if s.plans is None:
            s.plans = self._codecs
        s.radix = self.resolved_sort_backend == "radix"
        s.incremental = s.incremental and self.incremental
        s.stats = self.stats
        return s

    def add(self, chunk: np.ndarray, values=None) -> None:
        self._store().add(chunk, values if self.delta is not None else None)
        self.stream_version += 1

    def upsert(self, rows: np.ndarray, values=None) -> None:
        self._store().upsert(rows,
                             values if self.delta is not None else None)
        self.stream_version += 1

    def delete(self, rows: np.ndarray) -> None:
        self._store().delete(rows)
        self.stream_version += 1

    # -- snapshots ----------------------------------------------------------

    def _padded(self):
        s = self.state
        buf, vals = s.table()
        count = s.count
        cap = RS.snapshot_cap(count)
        buf, vals = RS.padded_table(buf, vals, cap)
        return buf, vals, count, cap

    def snapshot(self, full_remine: bool = False) -> P.PipelineResult:
        """Current cluster set of the survivor table (exact; padding is
        idempotent).

        ``full_remine=True`` forces the one-shot batch path (device
        sorts) — the baseline the incremental path is verified and
        benchmarked against."""
        if self.state is None or self.state.count == 0:
            raise RS.NoDataError("no data ingested")
        self.snapshot_stream_version = self.stream_version
        s = self._store()
        if full_remine or not s.incremental:
            s.compact()          # survivor set only; leave runs unmerged
        else:
            s.prepare()
        if s.count == 0:
            raise ValueError("no live rows (everything deleted)")
        buf, vals, count, cap = self._padded()
        self.stats["snapshots"] += 1
        import jax.numpy as jnp
        targs = jnp.asarray(buf, jnp.int32)
        vargs = None if vals is None else jnp.asarray(vals, jnp.float32)
        if full_remine or not s.incremental:
            self.stats["full_resorts"] += 1
            res = self._fn(targs, self._lo, self._hi, values=vargs)
        else:
            perms = s.perms(cap)
            if self.window_budget and self.packed_active:
                # windowed snapshot remine (DESIGN.md §3c): the merged
                # perms feed the bounded device window loop instead of
                # one monolithic O(T) pipeline call — bit-identical
                from . import windowed as WD
                res = WD.mine_windowed(
                    buf, vals, perms, plans=self.key_plans,
                    hash_lo=self._lo, hash_hi=self._hi, delta=self.delta,
                    theta=self.theta, minsup=self.minsup,
                    window_budget=self.window_budget,
                    sort_backend=self.resolved_sort_backend,
                    use_pallas=self.use_pallas)
            else:
                res = self._fn(targs, self._lo, self._hi, values=vargs,
                               perms=jnp.asarray(perms, jnp.int32))
        if self.track_dirty_sigs:
            self._note_sigs(res)
        return res

    def _note_sigs(self, result) -> None:
        """Record this snapshot's kept-signature set and how many
        signatures changed vs the previous snapshot (the serving
        layer's delta-index workload)."""
        sigs = P.kept_sig_words(result)
        self.last_dirty_sigs = P.dirty_sig_count(self.last_kept_sigs, sigs)
        self.last_kept_sigs = sigs

    def snapshot_clusters(self, only_kept: bool = True):
        return self.materialise(self.snapshot(), only_kept=only_kept)
