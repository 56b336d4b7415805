"""The comparison that decides ``correct`` for a mined table.

Every number compared has a limit of its own, kept in the cell's file
(``limits``); a run is correct when no number exceeds its limit.  The
numbers:

* ``sorted_e_mismatch`` — entries of the per-mode sorted entity columns
  (Stage 1's sort) that differ from the reference; exact, limit 0.
* ``range_mismatch`` — component window bounds of the kept clusters
  (the component operators and Stage 2's gather) that differ; exact,
  limit 0.
* ``keep_mismatch`` — tuples whose kept flag differs (Stage 3's dedup
  and the density filter); exact, limit 0.
* ``density_rel_gap`` — the largest relative gap of a kept cluster's
  float32 density from the exact ``#generating / volume``.
* ``repeat_mismatch`` — elements in which the window's last mine
  differs from its first; exact, limit 0.
"""
from __future__ import annotations

import numpy as np

FIELDS = ("keep", "density", "range_lo", "range_hi", "sorted_e")


def repeat_mismatch(first: dict, last: dict) -> int:
    """Elements that differ bit for bit between two fetched results."""
    return int(sum(np.count_nonzero(np.asarray(first[f]) != np.asarray(last[f]))
                   for f in FIELDS))


def mine_numbers(got: dict, want: dict) -> dict:
    """The compared numbers of one fetched result against the reference."""
    keep = np.asarray(got["keep"]).astype(bool)
    ref_keep = np.asarray(want["keep"]).astype(bool)
    both = keep & ref_keep
    rng = 0
    for f in ("range_lo", "range_hi"):
        rng += int(np.count_nonzero(
            np.asarray(got[f])[:, both].astype(np.int64)
            != np.asarray(want[f])[:, both]))
    ref_d = np.asarray(want["density"], np.float64)[both]
    d = np.asarray(got["density"]).astype(np.float64)[both]
    gap = float(np.max(np.abs(d - ref_d) / ref_d)) if ref_d.size else 0.0
    return {
        "sorted_e_mismatch": int(np.count_nonzero(
            np.asarray(got["sorted_e"]) != np.asarray(want["sorted_e"]))),
        "range_mismatch": rng,
        "keep_mismatch": int(np.count_nonzero(keep != ref_keep)),
        "density_rel_gap": gap,
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: [number, limit]}).  A number without a limit, or
    a limit without a number, is not correct."""
    names = sorted(set(numbers) | set(limits))
    table = {n: [numbers.get(n), limits.get(n)] for n in names}
    ok = all(v is not None and lim is not None and v <= lim
             for v, lim in table.values())
    return ok, table

