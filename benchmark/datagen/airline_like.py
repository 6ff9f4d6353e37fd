"""Synthetic rows of the Airline on-time shape (Table 2 of
arXiv:1806.11248: 115M x 13, binary "arrival delayed"): integer-coded
float32 columns that are mostly ties.  The real files cannot be fetched
here; every count below is quoted from memory of them, and the name of
a configuration that uses this says synthetic.

    column              values
     0 Year             22 (1987..2008), uniform
     1 Month            12, uniform
     2 DayofMonth       31, uniform
     3 DayOfWeek        7, uniform
     4 CRSDepTime       valid HHMM, 1,440: busy from 06 to 22 o'clock
     5 CRSArrTime       CRSDepTime plus the flight's time, HHMM
     6 UniqueCarrier    29 codes, Zipf (exponent 1) over a shuffled order
     7 FlightNum        8,000 codes, Zipf
     8 ActualElapsedTime  minutes, log-normal, rounded
     9 Origin           350 codes, Zipf
    10 Dest             350 codes, Zipf
    11 Distance         miles, log-normal, rounded
    12 Diverted         1 in 0.2 % of rows, else 0

The label is a linear score over per-code effects and the standardised
time, length and calendar columns, with three interactions, plus
logistic-like noise; the threshold is the 55th percentile of a pilot
sample, so about 45 % of rows are positive.  Everything is a function of
`seed`; every seed has the same sizes.  Rows are made in blocks of 2^20,
each from its own child of the seed, over a few threads; no float64 copy
of the matrix is made.  The
held-out rows are the last `n_held` (rows are i.i.d.).

    generate(seed, n_train, n_held, n_features)
        -> dict(X_train, y_train, X_held, y_held)   # float32, C order
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

COLUMNS = ("Year", "Month", "DayofMonth", "DayOfWeek", "CRSDepTime",
           "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
           "Origin", "Dest", "Distance", "Diverted")
N_CODES = {"UniqueCarrier": 29, "FlightNum": 8000, "Origin": 350, "Dest": 350}
DIVERTED_SHARE = 0.002
POSITIVE_SHARE = 0.45
_BLOCK = 1 << 20
_PILOT = 1 << 18
_THREADS = max(1, min(8, (os.cpu_count() or 2) - 1))


def _model(rng) -> dict:
    """What does not depend on the rows: each code column's popularity
    (Zipf over a shuffled order, as a CDF) and effect, the calendar
    effects and the weights of the score."""
    m = {}
    for name, n in N_CODES.items():
        p = 1.0 / np.arange(1, n + 1)
        p = p[rng.permutation(n)]           # popularity is not in code order
        m[name] = {"cdf": np.cumsum(p / p.sum()),
                   "effect": rng.normal(0.0, 1.0, n)}
    m["month"] = rng.normal(0.0, 1.0, 12)
    m["dow"] = rng.normal(0.0, 1.0, 7)
    m["year"] = rng.normal(0.0, 1.0, 22)
    m["w"] = rng.uniform(0.5, 1.5, 10) * rng.choice([-1.0, 1.0], 10)
    m["c"] = rng.uniform(0.5, 1.0, 3)
    return m


def _block(m: dict, rng, n: int):
    """(X float32 (n, 13), score float64 (n,)) of n fresh rows."""
    f32 = np.float32
    X = np.empty((n, len(COLUMNS)), f32)
    year = rng.integers(0, 22, n)
    month = rng.integers(0, 12, n)
    dow = rng.integers(0, 7, n)
    X[:, 0] = year + 1987
    X[:, 1] = month + 1
    X[:, 2] = rng.integers(1, 32, n)
    X[:, 3] = dow + 1
    # departures: a quarter spread over the whole day, the rest around
    # one o'clock in the afternoon; whole minutes
    dep = np.where(rng.random(n, dtype=f32) < 0.25,
                   rng.random(n, dtype=f32) * 1440.0,
                   780.0 + 240.0 * rng.standard_normal(n, dtype=f32))
    dep = np.mod(np.floor(dep), 1440.0)
    codes = {}
    for name in N_CODES:
        codes[name] = np.minimum(
            np.searchsorted(m[name]["cdf"], rng.random(n)),
            N_CODES[name] - 1)
    dist = np.clip(np.rint(np.exp(6.4 + 0.75 * rng.standard_normal(n, dtype=f32))),
                   11.0, 4962.0)
    air = np.clip(np.rint(30.0 + dist / 7.5 + 12.0 * np.exp(
        0.5 * rng.standard_normal(n, dtype=f32))), 15.0, 900.0)
    arr = np.mod(dep + air, 1440.0)
    X[:, 4] = np.floor(dep / 60.0) * 100.0 + np.mod(dep, 60.0)
    X[:, 5] = np.floor(arr / 60.0) * 100.0 + np.mod(arr, 60.0)
    X[:, 6] = codes["UniqueCarrier"]
    X[:, 7] = codes["FlightNum"]
    X[:, 8] = air
    X[:, 9] = codes["Origin"]
    X[:, 10] = codes["Dest"]
    X[:, 11] = dist
    div = rng.random(n, dtype=f32) < DIVERTED_SHARE
    X[:, 12] = div
    # the score: delays grow through the day and with a carrier's, an
    # airport's and a month's own effect
    hour = (dep.astype(np.float64) - 780.0) / 300.0
    ldist = (np.log(dist.astype(np.float64)) - 6.4) / 0.75
    carrier = m["UniqueCarrier"]["effect"][codes["UniqueCarrier"]]
    origin = m["Origin"]["effect"][codes["Origin"]]
    dest = m["Dest"]["effect"][codes["Dest"]]
    w, c = m["w"], m["c"]
    score = (w[0] * hour + w[1] * ldist + w[2] * carrier + w[3] * origin
             + w[4] * dest + w[5] * m["month"][month] + w[6] * m["dow"][dow]
             + w[7] * m["year"][year]
             + w[8] * 0.3 * m["FlightNum"]["effect"][codes["FlightNum"]]
             + w[9] * (air.astype(np.float64) - dist / 7.5 - 45.0) / 15.0
             + c[0] * hour * carrier + c[1] * origin * m["month"][month]
             + c[2] * ldist * (dow >= 5))
    score += 8.0 * div                      # a diverted flight arrives late
    score += 2.5 * rng.logistic(size=n)
    return X, score


def generate(seed: int, n_train: int, n_held: int, n_features: int) -> dict:
    if int(n_features) != len(COLUMNS):
        raise ValueError(f"the Airline shape has {len(COLUMNS)} columns, "
                         f"not {n_features}")
    seed = int(seed)
    m = _model(np.random.Generator(np.random.PCG64([seed, 0])))
    _, pilot = _block(m, np.random.Generator(np.random.PCG64([seed, 1])),
                      _PILOT)
    threshold = float(np.quantile(pilot, 1.0 - POSITIVE_SHARE))
    n = int(n_train) + int(n_held)
    X = np.empty((n, len(COLUMNS)), np.float32)
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
