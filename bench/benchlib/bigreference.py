"""Plain reference of OAC-family triclustering written for tens of
millions of rows, in numpy, independent of the program under test, and
the comparison of a result that carries cluster signatures rather than
component windows (``DistributedResult``).

Semantics, as ``reference.py`` states them (paper §3.1, §3.2, §4.1
Alg. 2-7, §4.3): tuple i's component along mode k is the set of
entities e with i's other columns whose value lies within ``delta`` of
i's (NOAC; the whole set for prime); clusters are equal when every
component is the same set; a cluster's density is ``#distinct
generating tuples / volume``; it is kept once, at its lowest-index
generating tuple among the first occurrences of distinct rows, when its
density is at least ``theta`` and every component has at least
``minsup`` entities.

How it scales: each mode's order is one sort of a packed int64 key
(other columns, value rank, entity); a tuple's window is found by
``searchsorted`` over the key with the entity dropped; equal windows are
neighbours in that order.  Set identity is exact: windows are grouped by
a 64-bit sum of random entity weights and every member of a group is
then compared with the group's first element by element; a group that
fails is split by its exact contents.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: seed of the entity weights that group candidate equal sets
WEIGHT_SEED = 0x5E75


def _bits(n: int) -> int:
    return max(1, int(n - 1).bit_length())


def _weights(n: int) -> np.ndarray:
    """Random 64-bit weight of each entity id: a window's set sums them
    (mod 2**64) to a candidate identity that is then verified."""
    return np.random.default_rng(WEIGHT_SEED).integers(
        0, 2**63, n, dtype=np.int64).astype(np.uint64)


def _mode_key(tuples, k, rank, bits, rank_bits):
    """(key, entity-field width) of mode k: the other columns in
    ascending column order most significant, then the value rank, then
    e_k."""
    n = tuples.shape[1]
    key = np.zeros(tuples.shape[0], np.int64)
    for j in [j for j in range(n) if j != k]:
        key = key << bits[j] | tuples[:, j]
    if rank is not None:
        key = key << rank_bits | rank
    return key << bits[k] | tuples[:, k], bits[k]


def _windows(key, e_bits, rank_bits, domain, delta):
    """Sorted order of one mode and each sorted position's window
    ``[lo, hi)`` of that order."""
    order = np.argsort(key)
    head = key[order] >> e_bits           # subrelation key [+ rank]
    if domain is None:
        lo = np.searchsorted(head, head, side="left")
        hi = np.searchsorted(head, head, side="right")
        return order, lo, hi
    d = np.float32(delta)
    lo_rank = np.searchsorted(domain, domain - d, side="left")
    hi_rank = np.searchsorted(domain, domain + d, side="right")
    rank = head & ((1 << rank_bits) - 1)
    seg = head >> rank_bits << rank_bits
    lo = np.searchsorted(head, seg + lo_rank[rank], side="left")
    hi = np.searchsorted(head, seg + hi_rank[rank], side="left")
    return order, lo, hi


def _set_ids(sorted_e, lo, hi, n_entities: int):
    """Exact identity (equal ids <=> equal entity sets) and size of the
    set of each sorted position's window.  Windows never decrease along
    the order, so equal windows are neighbours."""
    t = lo.size
    new = np.ones(t, bool)
    new[1:] = (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])
    w_of = np.cumsum(new) - 1
    wlo, whi = lo[new], hi[new]
    lens = whi - wlo
    owner = np.repeat(np.arange(wlo.size, dtype=np.int64), lens)
    pos = np.arange(owner.size, dtype=np.int64) - np.repeat(
        np.cumsum(lens) - lens, lens) + np.repeat(wlo, lens)
    e_bits = _bits(n_entities)
    pairs = np.sort(owner << e_bits | sorted_e[pos].astype(np.int64))
    keep = np.ones(pairs.size, bool)
    keep[1:] = pairs[1:] != pairs[:-1]
    pairs = pairs[keep]
    owner, ents = pairs >> e_bits, pairs & ((1 << e_bits) - 1)
    card = np.bincount(owner, minlength=wlo.size)
    start = np.cumsum(card) - card
    csum = np.concatenate([[np.uint64(0)],
                           np.cumsum(_weights(n_entities)[ents])])
    gid, by_h, head = _dense(csum[start + card] - csum[start])
    # every window against one of its group's, element by element
    rep = by_h[head][gid]
    el = (card == card[rep])[owner]
    at = start[rep[owner[el]]] + np.arange(owner.size)[el] - start[owner[el]]
    el[el] = ents[el] == ents[at]
    nxt = int(gid.max()) + 1
    for g in np.unique(gid[owner[~el]]):      # hash collisions: rare
        ids = {}
        for w in np.flatnonzero(gid == g):
            s = ents[start[w]:start[w] + card[w]].tobytes()
            if s not in ids:
                ids[s] = g if not ids else nxt
                nxt += len(ids) > 1
            gid[w] = ids[s]
    return gid[w_of], card[w_of]


def _dense(key: np.ndarray):
    """(ids, order, head): dense ids of ``key``'s distinct values in
    ascending order, the sorting permutation, and which sorted
    positions start a value."""
    order = np.argsort(key)
    sk = key[order]
    head = np.r_[True, sk[1:] != sk[:-1]]
    ids = np.empty(key.size, np.int64)
    ids[order] = np.cumsum(head) - 1
    return ids, order, head


def _combine(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Dense ids of the pairs (a, b)."""
    return _dense(a << _bits(int(b.max()) + 1) | b)[0]


def _mode(tuples, k, rank, bits, rank_bits, domain, delta):
    """Mode k's set id and set size per tuple, and (mode 0, whose key
    covers the row) which tuples are the first occurrence of their
    row."""
    t = tuples.shape[0]
    key, e_bits = _mode_key(tuples, k, rank, bits, rank_bits)
    order, lo, hi = _windows(key, e_bits, rank_bits, domain, delta)
    sid, card = _set_ids(tuples[order, k], lo, hi, 1 << bits[k])
    sids, cards = np.empty(t, np.int64), np.empty(t, np.int64)
    sids[order], cards[order] = sid, card
    first = None
    if k == 0:
        # the lowest index of each run of equal keys
        sk = key[order]
        first = np.zeros(t, bool)
        first[np.minimum.reduceat(
            order, np.flatnonzero(np.r_[True, sk[1:] != sk[:-1]]))] = True
    return sids, cards, first


def mine(tuples: np.ndarray, values=None, *, delta=None, theta: float = 0.0,
         minsup: int = 0, density_dtype=np.float64) -> dict:
    """Reference answer of one mine: ``keep`` (T,) bool, ``density``
    (T,) in ``density_dtype``, ``cardinalities`` (N, T) and ``cluster``
    (T,), the exact identity of each tuple's cluster as a dense id."""
    tuples = np.asarray(tuples, np.int32)
    t, n = tuples.shape
    sizes = [int(tuples[:, j].max()) + 1 for j in range(n)]
    bits = [_bits(s) for s in sizes]
    domain = rank = None
    rank_bits = 0
    if values is not None:
        if delta is None:
            raise ValueError("a valued table needs delta")
        values = np.asarray(values, np.float32)
        domain, rank = np.unique(values, return_inverse=True)
        rank = rank.ravel().astype(np.int64)
        rank_bits = _bits(domain.size + 1)
    if sum(bits) + rank_bits > 63:
        raise ValueError("the table's key does not fit 63 bits")
    with ThreadPoolExecutor(n) as pool:       # numpy's sorts run apart
        modes = list(pool.map(lambda k: _mode(tuples, k, rank, bits,
                                              rank_bits, domain, delta),
                              range(n)))
    first = modes[0][2]
    cards = np.stack([m[1] for m in modes])
    cid = modes[0][0]
    for k in range(1, n):
        cid = _combine(cid, modes[k][0])
    del modes
    n_clusters = int(cid.max()) + 1
    idx = np.flatnonzero(first)
    gen = np.bincount(cid[idx], minlength=n_clusters)
    rep = np.full(n_clusters, t, np.int64)
    np.minimum.at(rep, cid[idx], idx)
    volume = np.prod(cards, axis=0).astype(np.float64)
    density = (gen[cid] / np.maximum(volume, 1.0)).astype(density_dtype)
    keep = first & (rep[cid] == np.arange(t))
    keep &= density.astype(np.float64) >= theta
    if minsup:
        keep &= (cards >= minsup).all(axis=0)
    return {"keep": keep, "density": density, "cardinalities": cards,
            "cluster": cid}


def mine_config(params: dict, tuples, values=None,
                density_dtype=np.float64) -> dict:
    """:func:`mine` with a configuration's ``mine`` parameters."""
    return mine(tuples, values, delta=params.get("delta"),
                theta=params.get("theta", params.get("rho_min", 0.0)),
                minsup=params.get("minsup", 0), density_dtype=density_dtype)


# -- the comparison ----------------------------------------------------------

#: the fields of a mined result the comparison reads
FIELDS = ("keep", "density", "sig_lo", "sig_hi", "cardinalities")


def signature(got: dict) -> np.ndarray:
    """The 64-bit cluster signature of each tuple."""
    lo = np.asarray(got["sig_lo"]).astype(np.uint32).astype(np.uint64)
    hi = np.asarray(got["sig_hi"]).astype(np.uint32).astype(np.uint64)
    return hi << np.uint64(32) | lo


def cluster_mismatch(sig: np.ndarray, cluster: np.ndarray) -> int:
    """Tuples of the clusters at fault where the signatures do not map
    one to one onto the exact cluster identities (dense ids): a cluster
    under two signatures, or a cluster whose signature another cluster
    has too.  0 exactly when the map is one to one."""
    n = int(cluster.max()) + 1
    own = np.empty(n, sig.dtype)
    own[cluster] = sig                     # one signature of each cluster
    bad = np.zeros(n, bool)
    bad[cluster[sig != own[cluster]]] = True
    s = np.sort(own)
    shared = s[1:][s[1:] == s[:-1]]
    if shared.size:
        at = np.minimum(np.searchsorted(shared, own), shared.size - 1)
        bad |= shared[at] == own
    return int(np.count_nonzero(bad[cluster]))


def numbers(got: dict, want: dict) -> dict:
    """The compared numbers of a fetched result (``FIELDS``, over the
    table's rows) against :func:`mine`'s answer."""
    keep = np.asarray(got["keep"]).astype(bool)
    ref_keep = np.asarray(want["keep"]).astype(bool)
    either, both = keep | ref_keep, keep & ref_keep
    cards = np.asarray(got["cardinalities"]).astype(np.int64)
    ref_d = np.asarray(want["density"], np.float64)[both]
    d = np.asarray(got["density"]).astype(np.float64)[both]
    return {
        "keep_mismatch": int(np.count_nonzero(keep != ref_keep)),
        "card_mismatch": int(np.count_nonzero(
            cards[:, either] != want["cardinalities"][:, either])),
        "cluster_mismatch": cluster_mismatch(signature(got),
                                             want["cluster"]),
        "density_rel_gap": (float(np.max(np.abs(d - ref_d) / ref_d))
                            if ref_d.size else 0.0),
    }


def repeat_mismatch(first: dict, last: dict) -> int:
    """Elements that differ bit for bit between two fetched results."""
    return int(sum(np.count_nonzero(np.asarray(first[f])
                                    != np.asarray(last[f]))
                   for f in FIELDS))


def as_result(want: dict, density) -> dict:
    """The reference's answer in the fields a mined result is read
    with, its cluster id as the signature (the control)."""
    cid = np.asarray(want["cluster"], np.int64)
    return {"keep": want["keep"], "density": density,
            "cardinalities": want["cardinalities"],
            "sig_lo": (cid & 0xFFFFFFFF).astype(np.uint32),
            "sig_hi": (cid >> 32).astype(np.uint32)}
