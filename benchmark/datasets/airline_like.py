"""airline_like — seeded data at the shape of the ASA Data Expo 2009 airline
on-time table as szilard/benchm-ml reads it (no network on the chip
machine): eight inputs, six of them categorical, and the binary target
`dep_delayed_15min`. A configuration names this module under `data`, as
higgs_like.

  Month 12 levels, DayofMonth 31, DayOfWeek 7, DepTime hhmm 0..2359
  (numeric), UniqueCarrier 29, Origin 340, Dest 340, Distance miles
  (numeric) — `LEVELS`; the level counts of the three wide columns are
  ASSUMED (the configuration's file lists them): what matters to the
  program is that Origin and Dest hold more levels than a code byte.

Level frequencies are Zipf-like (a few hubs and carriers take most
flights), Distance is a function of the (origin, destination) pair, and
the label is a logistic of per-level effects of carrier, origin,
destination and month plus a rise of delay with DepTime, so a tree splits
on the wide columns. Every value of the eight inputs fits int16, and the
host table is held so (2 GB for the whole table against 4 GB in f32): the
categorical columns hold LEVEL IDS, which is what the frames are built
from (`Vec.from_numpy(ids, type=T_CAT, domain=...)`, no string look-up)
and what the reference walks.

Rows are made in fixed blocks, each from (seed, block), so the first n
rows are the same whatever the total; the level tables come from the seed
alone.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

LABEL, DOMAIN = "dep_delayed_15min", ["N", "Y"]
NAMES = ["Month", "DayofMonth", "DayOfWeek", "DepTime", "UniqueCarrier",
         "Origin", "Dest", "Distance"]
# levels of each input, 0: numeric
LEVELS = [12, 31, 7, 0, 29, 340, 340, 0]
BLOCK = 250_000


def feature_names(cols: int):
    return NAMES[:cols]


def domain_of(j: int):
    """The level names of input j: "c-1".."c-12" as benchm-ml's files have
    the calendar columns, codes for carriers and airports."""
    k = LEVELS[j]
    if j < 3:
        return [f"c-{i + 1}" for i in range(k)]
    return [f"{'CR' if j == 4 else 'AP'}{i:03d}" for i in range(k)]


def tables(seed: int) -> dict:
    """What the seed fixes for every row: level frequencies (cumulative),
    the airports' places, the levels' effects on the delay."""
    rng = np.random.default_rng([seed, 0xA1B])
    t = {}
    for j, k in enumerate(LEVELS):
        if k == 0:
            continue
        # calendar columns near uniform, carriers and airports Zipf-like
        w = 1.0 + 0.1 * rng.random(k) if j < 3 else \
            1.0 / (1.0 + rng.permutation(k)) ** 0.9
        t["cdf", j] = np.cumsum(w / w.sum()).astype(np.float32)
    k = LEVELS[5]
    t["place"] = np.stack([rng.random(k) * 2500.0, rng.random(k) * 1300.0], 1)
    # departures through the day: few at night, a morning and an evening peak
    hours = np.array([1, 1, 1, 1, 2, 6, 14, 16, 15, 14, 13, 13, 13, 13, 13,
                      14, 15, 16, 15, 12, 9, 6, 3, 2], np.float64)
    t["hour_cdf"] = np.cumsum(hours / hours.sum()).astype(np.float32)
    for j, scale in ((0, 0.25), (4, 0.45), (5, 0.6), (6, 0.4)):
        e = scale * rng.standard_normal(LEVELS[j])
        # centred on the flights, so that about a fifth are late whichever
        # hubs the seed made slow
        p = np.diff(t["cdf", j].astype(np.float64), prepend=0.0)
        t["effect", j] = (e - (p * e).sum()).astype(np.float32)
    return t


def host_arrays(rows: int, cols: int, seed: int):
    """(X (rows, cols) int16, y (rows,) bool) from the seed."""
    assert cols == len(NAMES), "the airline table has eight inputs"
    X = np.empty((rows, cols), np.int16)
    y = np.empty(rows, bool)
    t = tables(seed)

    def fill(b):
        lo, hi = b * BLOCK, min((b + 1) * BLOCK, rows)
        m = hi - lo
        rng = np.random.default_rng([seed, b])
        x = X[lo:hi]
        for j, k in enumerate(LEVELS):
            if k:
                x[:, j] = np.minimum(np.searchsorted(
                    t["cdf", j], rng.random(m, dtype=np.float32)), k - 1)
        hour = np.minimum(np.searchsorted(
            t["hour_cdf"], rng.random(m, dtype=np.float32)), 23)
        x[:, 3] = hour * 100 + rng.integers(0, 60, m)
        d = t["place"][x[:, 5]] - t["place"][x[:, 6]]
        x[:, 7] = np.clip(np.hypot(d[:, 0], d[:, 1]), 11, 4962)
        logit = (-2.05 + t["effect", 0][x[:, 0]] + t["effect", 4][x[:, 4]]
                 + t["effect", 5][x[:, 5]] + t["effect", 6][x[:, 6]]
                 + 1.1 * (x[:, 3].astype(np.float32) / 2400.0) ** 2)
        y[lo:hi] = rng.random(m, dtype=np.float32) \
            < 1.0 / (1.0 + np.exp(-logit))

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(-(-rows // BLOCK))))
    return X, y


def frame(X, y):
    """A Frame in the DKV through the public constructors: one Vec per
    host column, a categorical one from its level ids and its domain, the
    label a two-level categorical."""
    import jax
    from h2o3_tpu.core.frame import Frame, T_CAT, Vec

    def vec(j):
        if j == X.shape[1]:
            return Vec.from_numpy(y.astype(np.float64), type=T_CAT,
                                  domain=DOMAIN)
        if LEVELS[j]:
            return Vec.from_numpy(X[:, j], type=T_CAT, domain=domain_of(j))
        return Vec.from_numpy(X[:, j])

    # a column's packing is NumPy passes over its rows: a few threads wide
    with ThreadPoolExecutor(5) as pool:
        vecs = list(pool.map(vec, range(X.shape[1] + 1)))
    fr = Frame(feature_names(X.shape[1]) + [LABEL], vecs)
    jax.block_until_ready([v.data for v in fr.vecs])
    return fr
