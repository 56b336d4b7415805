"""The shuffle's dispatch packs records into each owner's send range in
their original order: bit for bit what a plain numpy loop gives."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.distributed import _dispatch


def _oracle(records, owner, n_shards, capacity):
    """Rank = the record's stable position among its owner's records,
    slot = owner × capacity + rank, records past capacity to the trash
    slot one past the end."""
    nslots = n_shards * capacity
    buf = np.zeros((nslots, records.shape[1]), records.dtype)
    valid = np.zeros((nslots,), bool)
    slot = np.full(owner.shape, nslots, np.int32)
    seen = np.zeros((n_shards,), np.int64)
    for r, o in enumerate(owner):
        if seen[o] < capacity:
            slot[r] = o * capacity + seen[o]
            buf[slot[r]] = records[r]
            valid[slot[r]] = True
        seen[o] += 1
    ok = slot < nslots
    return buf, valid, slot, ok, int((~ok).sum())


def _owners(case, n_shards, l, rng):
    if case == "balanced":
        return rng.permutation(np.arange(l) % n_shards)
    if case == "one_owner":
        return np.full(l, n_shards - 1)
    if case == "empty_owner":
        # owner 0 gets nothing (with one shard it takes every record)
        return rng.integers(min(1, n_shards - 1), n_shards, l)
    return rng.integers(0, n_shards, l)


def _capacity(cap, owner, n_shards, l):
    most = int(np.bincount(owner, minlength=n_shards).max())
    return {"one": 1, "largest": most, "largest_less_one": max(1, most - 1),
            "fair": -(-l // n_shards) * 2}[cap]


@pytest.mark.parametrize("n_shards", [1, 4, 8])
@pytest.mark.parametrize("case,cap", [
    ("balanced", "fair"),
    ("balanced", "one"),
    ("one_owner", "fair"),
    ("empty_owner", "largest"),
    ("random", "largest"),
    ("random", "largest_less_one"),
])
def test_dispatch_matches_numpy_oracle(n_shards, case, cap):
    rng = np.random.default_rng(n_shards * 100 + len(case) + len(cap))
    l = 203
    owner = _owners(case, n_shards, l, rng).astype(np.int32)
    capacity = _capacity(cap, owner, n_shards, l)
    records = rng.integers(0, 2**32, (l, 2), dtype=np.uint32)
    want = _oracle(records, owner, n_shards, capacity)
    got = _dispatch(jnp.asarray(records), jnp.asarray(owner), n_shards,
                    capacity)
    for name, w, g in zip(("buf", "valid", "slot", "ok", "overflow"),
                          want, got):
        g = np.asarray(g)
        assert g.dtype == np.asarray(w).dtype or name == "overflow", name
        np.testing.assert_array_equal(g, w, err_msg=name)
    if cap == "largest_less_one":
        assert want[4] > 0      # the case overflows
