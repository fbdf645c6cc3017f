"""An Airline-on-time-profile table from ``--seed``, for the configuration
``gbt-airline``. NumPy only; streams from ``datagen.rng`` (imported, not
edited) under tags of their own. No network: the columns and their
cardinalities are the source's as remembered, the rows synthesised.

Thirteen float32 columns in the source's order (:data:`FEATURES`), the
three categorical ones coded as integers:

- ``Year`` 1987..2008 (22 values), ``Month`` 12, ``DayofMonth`` 31,
  ``DayofWeek`` 7, uniform;
- ``CRSDepTime`` ``hhmm``: the hour from a lumpy day (few flights at
  night), the minute uniform (about 1,400 values); ``CRSArrTime`` the
  departure plus the scheduled time, wrapped at midnight;
- ``UniqueCarrier`` 29, ``Origin`` and ``Dest`` 340 each, under a Zipf
  skew (ids a seeded permutation of the ranks, so an id says nothing of
  its frequency);
- ``FlightNum`` 1..8,000, low numbers more often;
- ``Distance``: one of 1,600 distinct lengths, short ones more often;
  ``ActualElapsedTime``: minutes, the distance at cruising speed plus
  taxi time plus noise, 20..720 (about 700 values);
- ``Diverted``: 0.2 % ones.

Six columns therefore fill 2 to 31 of 256 quantile bins and seven fill
(nearly) all of them, and one bin of ``Diverted`` holds 99.8 % of the
rows.

**Labels** (``ArrDelay > 0``): a planted logit, the sum of seeded effects
of the carrier, the origin, the departure hour, the (carrier, origin,
hour) triple, the year, the month, the day of the week, and of how much
longer than its distance's plain time the flight took (distance x elapsed
time), plus logistic noise; positive where it is over 0. Between 45 and
50 % of the rows.

Rows are filled block by block on a few threads; block ``i`` always comes
from stream ``(seed, tag, i)``, so the bytes do not depend on the thread
count.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from benchmark import datagen

# Stream tags (datagen.py holds 1-4, the other generators 11-64).
TAG_TABLES, TAG_ROWS = 71, 72

FEATURES = ("Year", "Month", "DayofMonth", "DayofWeek", "CRSDepTime",
            "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
            "Origin", "Dest", "Distance", "Diverted")
YEARS, CARRIERS, AIRPORTS, FLIGHT_NUMBERS, DISTANCES = 22, 29, 340, 8000, 1600
DIVERTED_OF_65536 = 131          # 0.2 %
BIAS = -0.12                     # 45-50 % positive

_THREADS = 8
_BLOCK_ROWS = 1 << 19


def _draw_table(law: np.ndarray) -> np.ndarray:
    """``[65536]`` int32: the category of each of 2**16 equal slots, a
    category a run of slots in proportion to ``law`` (as ``word2vec.c``
    draws its negatives): sixteen random bits index a draw."""
    ends = np.cumsum(law, dtype=np.float64) / np.sum(law, dtype=np.float64)
    slots = (np.arange(1 << 16) + 0.5) / (1 << 16)
    return np.searchsorted(ends, slots).clip(0, len(law) - 1).astype(np.int32)


def _zipf(k: int, skew: float) -> np.ndarray:
    return _draw_table((np.arange(k, dtype=np.float64) + 2.0) ** -skew)


class _Tables:
    """What every block draws through: made once from ``seed``."""

    def __init__(self, seed: int):
        g = datagen.rng(seed, TAG_TABLES)
        self.carrier, self.airport = _zipf(CARRIERS, 1.0), _zipf(AIRPORTS, 1.1)
        self.carrier_id = g.permutation(CARRIERS).astype(np.float32)
        self.airport_id = g.permutation(AIRPORTS).astype(np.float32)
        # A lumpy day: an hour's share of the departures.
        self.hour = _draw_table(np.array(
            [1, 1, 1, 1, 1, 3, 9, 12, 12, 11, 10, 10, 10, 10, 10, 10, 11, 12,
             11, 9, 7, 5, 3, 2], np.float64))
        lengths = np.sort(g.choice(np.arange(31, 4963), DISTANCES, replace=False))
        self.distance = lengths.astype(np.float32)
        self.distance_rank = _zipf(DISTANCES, 0.6)

        def effect(scale, *draws):
            """Seeded effects of the categories the ``draws`` tables
            give, their mean over the rows taken off: the share of
            positive labels then hardly moves with the seed."""
            e = scale * g.standard_normal([int(d.max()) + 1 for d in draws])
            for axis, d in enumerate(draws):
                share = np.bincount(d, minlength=e.shape[axis]) / d.size
                e = e - np.tensordot(share, e, (0, axis)).reshape(
                    [1 if a == axis else n for a, n in enumerate(e.shape)])
            return e.reshape(-1).astype(np.float32)

        every = lambda n: np.arange(n, dtype=np.int32)   # each as often
        self.of_carrier, self.of_airport = effect(0.5, self.carrier), effect(0.4, self.airport)
        self.of_hour = (effect(0.1, self.hour)   # later in the day, later
                        + 0.06 * (np.arange(24) - 13.5)).astype(np.float32)
        self.of_triple = effect(0.4, self.carrier, self.airport, self.hour)
        self.of_year, self.of_month = effect(0.3, every(YEARS)), effect(0.3, every(12))
        self.of_weekday = effect(0.15, every(7))


def _fill(t: _Tables, seed: int, block: int, x: np.ndarray, y: np.ndarray) -> None:
    g = datagen.rng(seed, TAG_ROWS, block)
    m = x.shape[0]
    uniform = lambda: g.random(m, dtype=np.float32)
    below = lambda n: np.minimum((uniform() * n).astype(np.int32), n - 1)
    through = lambda table: table.take(g.integers(0, 1 << 16, m, dtype=np.uint16))
    year, month, weekday = below(YEARS), below(12), below(7)
    x[:, 0] = year + 1987
    x[:, 1] = month + 1
    x[:, 2] = below(31) + 1
    x[:, 3] = weekday + 1
    hour, minute = through(t.hour), below(60)
    x[:, 4] = hour * 100 + minute
    carrier, origin, dest = through(t.carrier), through(t.airport), through(t.airport)
    x[:, 6] = t.carrier_id[carrier]
    x[:, 7] = 1 + np.minimum((uniform() ** 2 * FLIGHT_NUMBERS).astype(np.int32),
                             FLIGHT_NUMBERS - 1)
    distance = t.distance[through(t.distance_rank)]
    plain = 25.0 + distance / 7.5          # taxi, and 450 miles an hour
    late = g.standard_normal(m, dtype=np.float32)
    elapsed = np.rint(plain * (np.float32(1.0) + np.float32(0.08) * late)).clip(20, 720)
    x[:, 8] = elapsed
    x[:, 9] = t.airport_id[origin]
    x[:, 10] = t.airport_id[dest]
    x[:, 11] = distance
    arrive = hour * 60 + minute + np.rint(plain).astype(np.int32)
    arrive %= 1440
    x[:, 5] = (arrive // 60) * 100 + arrive % 60
    x[:, 12] = g.integers(0, 1 << 16, m, dtype=np.uint16) < DIVERTED_OF_65536
    logit = (t.of_carrier[carrier] + t.of_airport[origin] + t.of_hour[hour]
             + t.of_triple[(carrier * AIRPORTS + origin) * 24 + hour]
             + t.of_year[year] + t.of_month[month] + t.of_weekday[weekday]
             + np.float32(6.0) * (elapsed - plain) / plain + np.float32(BIAS))
    u = uniform().clip(1e-7, 1 - 1e-7)          # logistic noise
    y[:] = logit + np.log(u / (1 - u)) > 0


def table(seed: int, rows: int):
    """``(x [rows, 13] float32, y [rows] float32 in {0, 1})``."""
    t = _Tables(seed)
    x = np.empty((rows, len(FEATURES)), np.float32)
    y = np.empty(rows, np.float32)
    with cf.ThreadPoolExecutor(_THREADS) as pool:
        for f in [pool.submit(_fill, t, seed, i, x[lo:lo + _BLOCK_ROWS],
                              y[lo:lo + _BLOCK_ROWS])
                  for i, lo in enumerate(range(0, rows, _BLOCK_ROWS))]:
            f.result()
    return x, y
