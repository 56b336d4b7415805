"""Plain reference of OAC-family triclustering over a whole table, in
numpy, independent of the program under test.

Semantics (paper §3.1, §3.2, §4.1 Alg. 2-7, §4.3):

* Each tuple i generates one cluster: along mode k its component is the
  set of entities e such that i with mode k replaced by e is in the
  table (prime), and whose value lies within ``delta`` of i's value
  (NOAC).
* Clusters are deduplicated by set equality of all their components.
* A cluster's density is ``#distinct generating tuples / volume``, the
  volume being the product of its component sizes.
* A cluster is kept once, at its lowest-index generating tuple among
  the first occurrences of distinct rows, when its density is at least
  ``theta`` and every component has at least ``minsup`` entities.

The answer is laid out the way the mined result is read back: per
mode, the entity column in the mode's sort order (other columns, then
value, then the entity) and each tuple's component as a half-open
window ``[lo, hi)`` of that order.  Set identity is decided exactly, by
comparing the windows' entity sets element for element, never by
hashes.
"""
from __future__ import annotations

import numpy as np


def _sorted_order(tuples: np.ndarray, k: int, values):
    """Row order of mode k: other columns (ascending column index) most
    significant, then the value, then the entity."""
    n = tuples.shape[1]
    keys = [tuples[:, k]]
    if values is not None:
        keys.append(values)
    keys += [tuples[:, j] for j in reversed(range(n)) if j != k]
    return np.lexsort(keys)


def _windows(tuples, k, values, delta):
    """(sorted entity column, lo, hi) of mode k, lo/hi per tuple in the
    table's own order."""
    t, n = tuples.shape
    order = _sorted_order(tuples, k, values)
    others = tuples[order][:, [j for j in range(n) if j != k]]
    start = np.ones(t, bool)
    start[1:] = (others[1:] != others[:-1]).any(axis=1)
    seg = np.cumsum(start) - 1
    seg_lo = np.flatnonzero(start)
    seg_hi = np.append(seg_lo[1:], t)
    if values is None:
        lo_s, hi_s = seg_lo[seg], seg_hi[seg]
    else:
        v = values[order].astype(np.float32)
        domain = np.unique(v)
        rank = np.searchsorted(domain, v)
        width = np.int64(domain.size + 1)
        combined = seg.astype(np.int64) * width + rank
        d = np.float32(delta)
        lo_rank = np.searchsorted(domain, v - d, side="left")
        hi_rank = np.searchsorted(domain, v + d, side="right")
        base = seg.astype(np.int64) * width
        lo_s = np.searchsorted(combined, base + lo_rank, side="left")
        hi_s = np.searchsorted(combined, base + hi_rank, side="left")
    lo = np.empty(t, np.int64)
    hi = np.empty(t, np.int64)
    lo[order] = lo_s
    hi[order] = hi_s
    return tuples[order, k].astype(np.int32), lo, hi


def _set_ids(sorted_e, lo, hi):
    """Exact identity of each tuple's entity set (equal ids <=> equal
    sets) and its cardinality."""
    win, w_of = np.unique(np.stack([lo, hi], 1), axis=0, return_inverse=True)
    w_of = w_of.ravel()
    lens = win[:, 1] - win[:, 0]
    owner = np.repeat(np.arange(win.shape[0], dtype=np.int64), lens)
    starts = np.repeat(win[:, 0] - np.cumsum(np.append(0, lens[:-1])), lens)
    ents = sorted_e[np.arange(owner.size) + starts].astype(np.int64)
    pairs = np.unique(owner << 32 | ents)
    owner, ents = pairs >> 32, pairs & 0xFFFFFFFF
    card = np.bincount(owner, minlength=win.shape[0])
    bounds = np.append(0, np.cumsum(card))
    sid = np.empty(win.shape[0], np.int64)
    next_id = 0
    for c in np.unique(card):
        members = np.flatnonzero(card == c)
        if c == 0:
            sid[members] = next_id
            next_id += 1
            continue
        rows = ents[bounds[members][:, None] + np.arange(c)]
        _, inv = np.unique(rows, axis=0, return_inverse=True)
        inv = inv.ravel()
        sid[members] = next_id + inv
        next_id += int(inv.max()) + 1
    return sid[w_of], card[w_of]


def mine(tuples: np.ndarray, values=None, *, delta=None, theta: float = 0.0,
         minsup: int = 0, density_dtype=np.float64) -> dict:
    """Reference answer of one mine: ``keep`` (T,) bool, ``density`` (T,)
    of each tuple's cluster in ``density_dtype``, ``range_lo`` /
    ``range_hi`` (N, T) and ``sorted_e`` (N, T).

    ``values`` is None for prime OAC; with values, ``delta`` selects the
    NOAC δ-operator and the table must hold each tuple once."""
    tuples = np.asarray(tuples, np.int32)
    t, n = tuples.shape
    if values is not None:
        values = np.asarray(values, np.float32)
        if delta is None:
            raise ValueError("a valued table needs delta")
    order = np.lexsort(tuples.T[::-1])
    new = np.ones(t, bool)
    new[1:] = (tuples[order][1:] != tuples[order][:-1]).any(axis=1)
    first = np.zeros(t, bool)
    first[order[new]] = True          # lowest index of each distinct row
    sorted_e = np.empty((n, t), np.int32)
    lo = np.empty((n, t), np.int64)
    hi = np.empty((n, t), np.int64)
    sids = np.empty((n, t), np.int64)
    cards = np.empty((n, t), np.int64)
    for k in range(n):
        sorted_e[k], lo[k], hi[k] = _windows(tuples, k, values, delta)
        sids[k], cards[k] = _set_ids(sorted_e[k], lo[k], hi[k])
    _, cid = np.unique(sids.T, axis=0, return_inverse=True)
    cid = cid.ravel()
    n_clusters = int(cid.max()) + 1
    gen = np.bincount(cid[first], minlength=n_clusters)
    rep = np.full(n_clusters, t, np.int64)
    np.minimum.at(rep, cid[first], np.flatnonzero(first))
    volume = np.prod(cards, axis=0).astype(np.float64)
    density = (gen[cid] / np.maximum(volume, 1.0)).astype(density_dtype)
    keep = first & (rep[cid] == np.arange(t))
    keep &= density.astype(np.float64) >= theta
    if minsup:
        keep &= (cards >= minsup).all(axis=0)
    return {"keep": keep, "density": density, "range_lo": lo,
            "range_hi": hi, "sorted_e": sorted_e}


def mine_config(params: dict, tuples, values=None,
                density_dtype=np.float64) -> dict:
    """:func:`mine` with a configuration's ``mine`` parameters (``theta``
    for prime, ``delta``, ``rho_min`` and ``minsup`` for NOAC)."""
    return mine(tuples, values, delta=params.get("delta"),
                theta=params.get("theta", params.get("rho_min", 0.0)),
                minsup=params.get("minsup", 0), density_dtype=density_dtype)
