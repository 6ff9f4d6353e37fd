"""Synthetic rows of GBM-perf's one-hot Airline job (github.com/szilard/
GBM-perf, grown out of benchm-ml): the Airline on-time columns Month,
DayofMonth, DayOfWeek, DepTime, UniqueCarrier, Origin, Dest, Distance,
label `dep_delayed_15min`, the six categoricals one-hot encoded.  The
real files cannot be fetched here; every count below is quoted from
memory of the source or set by the configuration (its `assumed`), and
the name of a configuration that uses this says synthetic.

    columns        width  values
      0 -  11 Month        12  one-hot, uniform
     12 -  42 DayofMonth   31  one-hot, uniform
     43 -  49 DayOfWeek     7  one-hot, uniform
     50      DepTime        1  valid HHMM: a quarter over the whole day,
                               the rest around one in the afternoon
     51 -  72 UniqueCarrier 22  one-hot, Zipf (exponent 1), shuffled order
     73 - 382 Origin      310  one-hot, Zipf
    383 - 693 Dest        311  one-hot, Zipf
    694      Distance       1  miles, log-normal, rounded

Every one-hot group holds exactly one 1.0 a row and 0.0 elsewhere
(float32): 695 columns, 693 of them two-valued.  The label is a linear
score over per-code effects and the standardised departure hour and
distance, with three interactions, plus logistic noise; the threshold
is the quantile of a pilot sample that leaves `POSITIVE_SHARE` of the
rows positive.  Everything is a function of `seed`; every seed has the
same sizes.  Rows are made in blocks of 2^16, each from its own child
of the seed, over a few threads (the block size, not the thread count,
fixes the draw).  The held-out rows are the last `n_held` (rows are
i.i.d.).

    generate(seed, n_train, n_held, n_features)
        -> dict(X_train, y_train, X_held, y_held)   # float32, C order
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from datagen.airline_like import _THREADS

# (name, codes) in the source's column order; None = one numeric column
GROUPS = (("Month", 12), ("DayofMonth", 31), ("DayOfWeek", 7),
          ("DepTime", None), ("UniqueCarrier", 22), ("Origin", 310),
          ("Dest", 311), ("Distance", None))
ZIPF = ("UniqueCarrier", "Origin", "Dest")
WIDTH = sum(n or 1 for _, n in GROUPS)
OFFSET = dict(zip((g for g, _ in GROUPS),
                  np.cumsum([0] + [n or 1 for _, n in GROUPS])[:-1].tolist()))
POSITIVE_SHARE = 0.19
_BLOCK = 1 << 16
_PILOT = 1 << 16


def _model(rng) -> dict:
    """What does not depend on the rows: each code column's popularity
    (uniform, or Zipf over a shuffled order, as a CDF) and effect, and
    the weights of the score."""
    m = {}
    for name, n in GROUPS:
        if n is None:
            continue
        p = 1.0 / np.arange(1, n + 1) if name in ZIPF else np.ones(n)
        p = p[rng.permutation(n)]           # popularity is not in code order
        m[name] = {"cdf": np.cumsum(p / p.sum()),
                   "effect": rng.normal(0.0, 1.0, n)}
    m["w"] = rng.uniform(0.5, 1.5, 8) * rng.choice([-1.0, 1.0], 8)
    m["c"] = rng.uniform(0.5, 1.0, 3)
    return m


def _block(m: dict, rng, n: int):
    """(X float32 (n, WIDTH), score float64 (n,)) of n fresh rows."""
    f32 = np.float32
    X = np.zeros((n, WIDTH), f32)
    rows = np.arange(n)
    code = {}
    for name, k in GROUPS:
        if k is None:
            continue
        code[name] = np.minimum(
            np.searchsorted(m[name]["cdf"], rng.random(n)), k - 1)
        X[rows, OFFSET[name] + code[name]] = 1.0
    dep = np.where(rng.random(n, dtype=f32) < 0.25,
                   rng.random(n, dtype=f32) * 1440.0,
                   780.0 + 240.0 * rng.standard_normal(n, dtype=f32))
    dep = np.mod(np.floor(dep), 1440.0)
    dist = np.clip(np.rint(np.exp(6.4 + 0.75 * rng.standard_normal(
        n, dtype=f32))), 11.0, 4962.0)
    X[:, OFFSET["DepTime"]] = np.floor(dep / 60.0) * 100.0 + np.mod(dep, 60.0)
    X[:, OFFSET["Distance"]] = dist
    # the score: delays grow through the day and with a carrier's, an
    # airport's and a month's own effect
    hour = (dep.astype(np.float64) - 780.0) / 300.0
    ldist = (np.log(dist.astype(np.float64)) - 6.4) / 0.75

    def eff(name):
        return m[name]["effect"][code[name]]
    w, c = m["w"], m["c"]
    score = (w[0] * hour + w[1] * ldist + w[2] * eff("UniqueCarrier")
             + w[3] * eff("Origin") + w[4] * eff("Dest")
             + w[5] * eff("Month") + w[6] * eff("DayOfWeek")
             + w[7] * 0.3 * eff("DayofMonth")
             + c[0] * hour * eff("UniqueCarrier")
             + c[1] * eff("Origin") * eff("Month")
             + c[2] * ldist * (code["DayOfWeek"] >= 5))
    score += 2.5 * rng.logistic(size=n)
    return X, score


def generate(seed: int, n_train: int, n_held: int, n_features: int) -> dict:
    if int(n_features) != WIDTH:
        raise ValueError(f"the one-hot Airline job has {WIDTH} columns, "
                         f"not {n_features}")
    seed = int(seed)
    m = _model(np.random.Generator(np.random.PCG64([seed, 0])))
    _, pilot = _block(m, np.random.Generator(np.random.PCG64([seed, 1])),
                      _PILOT)
    threshold = float(np.quantile(pilot, 1.0 - POSITIVE_SHARE))
    n = int(n_train) + int(n_held)
    X = np.empty((n, WIDTH), np.float32)
    y = np.empty(n, np.float32)

    def one(b: int) -> None:
        rows = slice(b * _BLOCK, min(n, (b + 1) * _BLOCK))
        rng = np.random.Generator(np.random.PCG64([seed, 2 + b]))
        X[rows], score = _block(m, rng, rows.stop - rows.start)
        y[rows] = score > threshold
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(one, range(-(-n // _BLOCK))))
    return {"X_train": X[:n_train], "y_train": y[:n_train],
            "X_held": X[n_train:], "y_held": y[n_train:]}
