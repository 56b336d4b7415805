"""Seeded tables of the benchmark's deployments.

``bibsonomy_like`` and ``_power_law_ids`` are copies of the program's
generators as they stood when the benchmark was defined
(``tests/test_bench_harness.py`` pins the copy against them for seed 0).
The benchmark keeps its own copies so that a later change to the
program's data module cannot move the yardstick.  ``ratings`` is the
benchmark's own.

A table is ``(sizes, tuples, values)``: mode cardinalities, an (T, N)
int32 array and a (T,) float32 value column or None.
"""
from __future__ import annotations

import numpy as np


def _power_law_ids(rng, n: int, count: int, alpha: float = 1.3):
    p = 1.0 / np.arange(1, n + 1) ** alpha
    p /= p.sum()
    return rng.choice(n, size=count, p=p).astype(np.int32)


def bibsonomy_like(n_tuples: int = 816_197, seed: int = 0,
                   scale: float = 1.0):
    """Users x tags x bookmarks of the paper's Table 2 (2,337 x 67,464 x
    28,920, 816,197 triples), power-law ids; ``scale`` shrinks every mode
    and the tuple count proportionally."""
    rng = np.random.default_rng(seed)
    nu = max(2, int(2337 * scale))
    nt = max(2, int(67464 * scale))
    nb = max(2, int(28920 * scale))
    t = max(1, int(n_tuples * scale))
    users = _power_law_ids(rng, nu, t, alpha=1.2)
    tags = _power_law_ids(rng, nt, t, alpha=1.4)
    bookmarks = _power_law_ids(rng, nb, t, alpha=1.1)
    return (nu, nt, nb), np.stack([users, tags, bookmarks], 1), None


def user_counts(users: int, rows: int, floor: int, top: int) -> np.ndarray:
    """Ratings per user, most active first: ``floor + (top - floor) *
    (i**-a - users**-a) / (1 - users**-a)``, rounded down, for the ranks
    i = 1..users, so the first user holds ``top`` and the last ``floor``;
    the exponent ``a`` is found so that the counts come to ``rows``, and
    the few ratings rounding leaves over go one each to the users ranked
    2, 3, ...  The same for every seed."""
    i = np.arange(1, users + 1, dtype=np.float64)

    def above_floor(a):
        share = (i ** -a - users ** -a) / (1 - users ** -a)
        return np.floor(np.maximum(0.0, (top - floor) * share))
    lo, hi = 1e-6, 8.0
    for _ in range(200):
        a = (lo + hi) / 2
        if above_floor(a).sum() > rows - floor * users:
            lo = a
        else:
            hi = a
    counts = floor + above_floor(hi).astype(np.int64)
    left = rows - int(counts.sum())
    if floor < 1 or not 0 <= left < users - 1:
        raise ValueError(f"no such power law gives {users} users of "
                         f"{floor} to {top} ratings {rows} in all")
    counts[1:1 + left] += 1
    return counts


def ratings(seed: int, users: int, movies: int, rated_movies: int,
            star_counts, user_floor: int, user_top: int,
            movie_alpha: float):
    """Users x movies x stars, one star per (user, movie) pair.

    Every seed gives the same work in another order: the per-user counts
    (``user_counts``), the movie popularity law and the number of
    ratings of each star are fixed; the seed draws which user and which
    movie hold which rank, each user's movies (without replacement, by
    weight ``rank**-movie_alpha`` over the ``rated_movies`` movies that
    have ratings) and which pair gets which star.  The star is both the
    third mode (0-4) and the value (1.0-5.0)."""
    rng = np.random.default_rng(seed)
    stars = np.repeat(np.arange(len(star_counts), dtype=np.int32),
                      np.asarray(star_counts, np.int64))
    counts = user_counts(users, stars.size, user_floor, user_top)
    if counts.max() > rated_movies:
        raise ValueError("a user would rate more movies than have ratings")
    who = rng.permutation(users).astype(np.int32)
    what = rng.permutation(movies)[:rated_movies].astype(np.int32)
    logw = -movie_alpha * np.log(np.arange(1, rated_movies + 1))
    u_out, m_out = [], []
    for lo in range(0, users, 256):          # 256 users' draws at a time
        k = counts[lo:lo + 256]
        # Gumbel top-k: the k largest of log w + Gumbel noise are a
        # weighted draw of k movies without replacement
        g = rng.gumbel(size=(k.size, rated_movies)) + logw
        for j, kj in enumerate(k.tolist()):
            m_out.append(what[np.argpartition(-g[j], kj - 1)[:kj]])
            u_out.append(np.full(kj, who[lo + j], np.int32))
    rng.shuffle(stars)
    tuples = np.stack([np.concatenate(u_out), np.concatenate(m_out), stars],
                      1)
    return (users, movies, len(star_counts)), tuples, \
        (stars + 1).astype(np.float32)


GENERATORS = {"bibsonomy_like": bibsonomy_like, "ratings": ratings}


def table_seed(seed: int) -> int:
    """``--seed`` as a generator seed: any whole number, negative ones
    folded into the unsigned 64-bit range."""
    return int(seed) % 2**64


def make_table(spec: dict, seed: int):
    """The table a ``table`` entry describes, from ``seed``."""
    kw = {k: v for k, v in spec.items() if k != "generator"}
    sizes, tuples, values = GENERATORS[spec["generator"]](
        seed=table_seed(seed), **kw)
    return (tuple(int(s) for s in sizes),
            np.ascontiguousarray(tuples, np.int32), values)
