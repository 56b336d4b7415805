"""Seeded ratings tables at MovieLens-25M's scale, made in seconds.

``ratings_by_rejection`` gives the table shape of ``tables.ratings`` —
users x movies x star ids, one star per (user, movie) pair, the same
per-user counts (``tables.user_counts``) and the same number of each
star for every seed — but draws each user's movies by rejection: a
stream of weighted draws with replacement, of which the user keeps the
first ``k`` distinct movies.  That is the law of successive weighted
draws without replacement, the law ``tables.ratings`` draws by a Gumbel
top-k over every rated movie; here the cost is in rows, not in users x
movies.  A user who would need more draws than there are movies takes
the Gumbel top-k, which has the same law.

Importing this module registers the generator in ``tables.GENERATORS``
under its name, so ``tables.make_table`` makes the table a configuration
names.
"""
from __future__ import annotations

import numpy as np

from . import tables

#: stream draws per distinct movie needed, on top of the expected count
OVERDRAW = 1.1


def _expected_draws(p: np.ndarray, need: np.ndarray) -> np.ndarray:
    """Draws with replacement after which ``need`` distinct movies are
    expected, by interpolation of sum(1 - exp(-n p)) over n."""
    grid = np.unique(np.geomspace(1, 1e9, 400).astype(np.int64))
    distinct = np.array([-np.expm1(-n * p).sum() for n in grid])
    return np.interp(need, distinct, grid)


def _first_distinct(users: np.ndarray, movies: np.ndarray, want: np.ndarray,
                    shift: int):
    """Of a user-major stream of draws, each user's first distinct
    movies in stream order, at most ``want[u]`` of them: (users,
    movies)."""
    key = users.astype(np.int64) << shift | movies
    _, first = np.unique(key, return_index=True)
    first.sort()
    u = users[first]
    start = np.searchsorted(u, u, side="left")
    take = (np.arange(u.size) - start) < want[u]
    return u[take], movies[first[take]]


def _rejection(rng, counts, p, cdf, shift):
    """(user, movie rank) rows of ``counts``' users, each user's
    ``counts[u]`` distinct ranks by rejection: a round draws each
    pending user's expected need (``OVERDRAW``), and a user still short
    continues its stream in the next round."""
    need = counts.astype(np.int64)
    draws = np.ceil(_expected_draws(p, need) * OVERDRAW).astype(np.int64) + 8
    pending = np.arange(need.size)
    part_u = part_m = np.empty(0, np.int64)   # the short users' draws
    done_u, done_m = [], []
    while pending.size:
        su = np.repeat(pending, draws[pending])
        sm = np.searchsorted(cdf, rng.random(su.size), side="right")
        if part_u.size:
            # each short user's earlier draws lead its stream
            su = np.concatenate([part_u, su])
            sm = np.concatenate([part_m, sm])
            order = np.argsort(su, kind="stable")
            su, sm = su[order], sm[order]
        u, m = _first_distinct(su, sm, need, shift)
        got = np.bincount(u, minlength=need.size)
        full = got[u] == need[u]
        done_u.append(u[full])
        done_m.append(m[full])
        part_u, part_m = u[~full], m[~full]
        pending = pending[got[pending] < need[pending]]
        draws[pending] = 2 * (need[pending] - got[pending]) + 8
    return np.concatenate(done_u), np.concatenate(done_m)


def _gumbel(rng, counts, logw):
    """(user, movie rank) rows by a Gumbel top-k over every movie."""
    us, ms = [], []
    for u, k in enumerate(counts.tolist()):
        g = rng.gumbel(size=logw.size) + logw
        ms.append(np.argpartition(-g, k - 1)[:k])
        us.append(np.full(k, u, np.int64))
    if not us:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    return np.concatenate(us), np.concatenate(ms)


def scaled(scale: float, users: int, star_counts) -> tuple:
    """(users, star counts) of a table shrunk by ``scale``: fewer users
    and each star's count times ``scale`` (at least one), the movies and
    the per-user floor and top kept, as a sample of the users would."""
    if scale == 1.0:
        return users, list(star_counts)
    return (max(2, int(users * scale)),
            [max(1, int(c * scale)) for c in star_counts])


def ratings_by_rejection(seed: int, users: int, movies: int,
                         rated_movies: int, star_counts, user_floor: int,
                         user_top: int, movie_alpha: float,
                         star_step: float = 1.0, scale: float = 1.0):
    """Users x movies x stars, one star per (user, movie) pair.

    Every seed gives the same work in another order: the per-user
    counts, the movie popularity law (weight ``rank**-movie_alpha`` over
    the ``rated_movies`` movies that have ratings) and the number of
    ratings of each star are fixed; the seed draws which user and which
    movie hold which rank, each user's movies and which pair gets which
    star.  The star id is the third mode and its value is ``(id + 1) *
    star_step``.  ``scale`` shrinks the table (``scaled``)."""
    users, star_counts = scaled(scale, users, star_counts)
    rated = rated_movies
    stars = np.repeat(np.arange(len(star_counts), dtype=np.int32),
                      np.asarray(star_counts, np.int64))
    counts = tables.user_counts(users, stars.size, user_floor, user_top)
    if counts.max() > rated:
        raise ValueError("a user would rate more movies than have ratings")
    rng = np.random.default_rng(seed)
    who = rng.permutation(users).astype(np.int32)
    what = rng.permutation(movies)[:rated].astype(np.int32)
    logw = -movie_alpha * np.log(np.arange(1, rated + 1))
    p = np.exp(logw - logw.max())
    p /= p.sum()
    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    heavy = _expected_draws(p, counts) * OVERDRAW > rated
    shift = max(1, int(rated - 1).bit_length())
    light = np.flatnonzero(~heavy)
    lu, lm = _rejection(rng, counts[light], p, cdf, shift)
    hu, hm = _gumbel(rng, counts[heavy], logw)
    u = np.concatenate([light[lu], np.flatnonzero(heavy)[hu]])
    m = np.concatenate([lm, hm])
    order = np.argsort(u, kind="stable")
    rng.shuffle(stars)
    tuples = np.stack([who[u[order]], what[m[order]], stars], 1)
    return (users, movies, len(star_counts)), tuples, \
        ((stars + 1) * np.float32(star_step)).astype(np.float32)


tables.GENERATORS.setdefault("ratings_by_rejection", ratings_by_rejection)
