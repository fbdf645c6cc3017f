"""A Yahoo! Music-profile rating table from ``--seed``, for the
configuration ``als-yahoomusic``. NumPy only; streams from
``datagen.rng`` (imported, not edited) under tags of their own. No
network: the counts are the source's, the skews synthesised.

- **Users** hold ``10 + C (rank + USER_SHIFT) ** -USER_SKEW`` ratings by
  their rank (Zipf-Mandelbrot over a floor of 10, the source's own: it
  keeps users with ten ratings or more), ``C`` set so that the whole is
  ``ratings``; the user of a rank is a seeded permutation's. The table is
  grouped by user, as the source's files are.
- **Items** are drawn a rating: with probability :data:`ITEM_FLAT` any
  item, else rank ``floor(x)`` with ``x`` Zipf-Mandelbrot on ``[0,
  items)`` (density ``(x + ITEM_SHIFT) ** -ITEM_SKEW``, by the inverse of
  its integral); the item of a rank is a seeded permutation's. An item no
  rating drew is written over one seeded rating, so the vocabulary is
  ``items`` exactly. The same (user, item) pair may come twice.
- **Ratings** are ``clip(round(50 + 10 b_u + 10 b_i + 7.5 p_u . q_i + 10
  e), 0, 100)``: biases, a planted rank-:data:`RANK` term (standard
  deviation 15) and noise, all standard normal from the seed.

At the source's counts (1,000,990 x 624,961, 252,800,275 ratings) the
heaviest user holds about 1e5 ratings, the heaviest item 0.24 % of all,
the median item about 46 (``configs/als-yahoomusic.json``: assumed).

Ratings are filled block by block on a few threads; block ``i`` always
comes from stream ``(seed, tag, i)``, so the bytes do not depend on the
thread count.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from benchmark import datagen

# Stream tags (datagen.py holds 1-4, datagen_criteo.py 11-13,
# datagen_mnist.py 21-23, datagen_fm.py 31-33).
TAG_IDS, TAG_PLANTED, TAG_ITEMS, TAG_NOISE, TAG_PATCH = 41, 42, 43, 44, 45

_THREADS = 8
_BLOCK = 1 << 20
USER_SKEW, USER_SHIFT, USER_FLOOR = 1.1, 500.0, 10
ITEM_SKEW, ITEM_SHIFT, ITEM_FLAT = 1.2, 100.0, 0.02
RANK = 4


def user_degrees(users: int, ratings: int) -> np.ndarray:
    """Ratings a user by rank, int64, summing to ``ratings`` exactly."""
    if ratings < USER_FLOOR * users:
        raise ValueError(f"{ratings} ratings are under {USER_FLOOR} a user of {users}")
    law = (np.arange(users, dtype=np.float64) + USER_SHIFT) ** -USER_SKEW
    degrees = USER_FLOOR + np.floor(
        (ratings - USER_FLOOR * users) / law.sum() * law).astype(np.int64)
    # What the floors dropped goes to the heaviest users, one each.
    degrees[:ratings - int(degrees.sum())] += 1
    return degrees


def _item_ranks(u: np.ndarray, items: int) -> np.ndarray:
    """Zipf-Mandelbrot ranks below ``items`` from uniforms ``u``."""
    e = 1.0 - ITEM_SKEW
    lo, hi = ITEM_SHIFT ** e, (items + ITEM_SHIFT) ** e
    x = (lo + u * (hi - lo)) ** (1.0 / e) - ITEM_SHIFT
    return np.clip(x, 0, items - 1).astype(np.int32)


def rating_table(seed: int, users: int, items: int, ratings: int):
    """``(user int32 [ratings], item int32 [ratings], rating float32
    [ratings])``, grouped by user; ids ``0 .. users - 1`` and ``0 ..
    items - 1``, every one present."""
    g = datagen.rng(seed, TAG_IDS)
    user_of_rank = g.permutation(users).astype(np.int32)
    item_of_rank = g.permutation(items).astype(np.int32)
    degrees = np.empty(users, np.int64)
    degrees[user_of_rank] = user_degrees(users, ratings)
    g = datagen.rng(seed, TAG_PLANTED)
    user_bias = g.standard_normal(users, dtype=np.float32)
    item_bias = g.standard_normal(items, dtype=np.float32)
    user_taste = g.standard_normal((users, RANK), dtype=np.float32)
    item_taste = g.standard_normal((items, RANK), dtype=np.float32)

    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(degrees, out=indptr[1:])
    user = np.empty(ratings, np.int32)
    item = np.empty(ratings, np.int32)
    rating = np.empty(ratings, np.float32)
    blocks = list(enumerate(range(0, ratings, _BLOCK)))

    def work(mine) -> None:
        for block, lo in mine:
            hi = min(lo + _BLOCK, ratings)
            # The users whose ratings this block's positions are.
            first = int(np.searchsorted(indptr, lo, side="right")) - 1
            last = int(np.searchsorted(indptr, hi, side="left"))
            bounds = np.clip(indptr[first:last + 1], lo, hi) - lo
            u = np.repeat(np.arange(first, last, dtype=np.int32), np.diff(bounds))
            g = datagen.rng(seed, TAG_ITEMS, block)
            flat = g.random(hi - lo) < ITEM_FLAT
            ranks = _item_ranks(g.random(hi - lo), items)
            ranks[flat] = g.integers(0, items, int(flat.sum()), dtype=np.int32)
            i = item_of_rank[ranks]
            score = 50.0 + 10.0 * (user_bias[u] + item_bias[i])
            score += 7.5 * np.einsum("nk,nk->n", user_taste[u], item_taste[i])
            score += 10.0 * datagen.rng(seed, TAG_NOISE, block).standard_normal(
                hi - lo, dtype=np.float32)
            user[lo:hi], item[lo:hi] = u, i
            rating[lo:hi] = np.clip(np.rint(score), 0.0, 100.0)

    with cf.ThreadPoolExecutor(_THREADS) as pool:
        # list(): an executor keeps a task's exception until it is read.
        list(pool.map(work, [blocks[t::_THREADS] for t in range(_THREADS)]))
    # Items no rating drew take one seeded rating each (at the source's
    # counts a few of 252.8 million; again, should a rating so taken
    # have been an item's only one, which small tables can see).
    for attempt in range(64):
        seen = np.zeros(items, bool)
        seen[item] = True
        missing = np.flatnonzero(~seen).astype(np.int32)
        if not missing.size:
            break
        at = datagen.rng(seed, TAG_PATCH, attempt).choice(
            ratings, missing.size, replace=False)
        item[at] = missing
    return user, item, rating
