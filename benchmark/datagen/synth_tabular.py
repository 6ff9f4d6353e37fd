"""Dense synthetic tabular data, `make_regression` / `make_classification`
in spirit: standard-normal float32 features, the first `n_informative`
carry a linear signal plus a few pairwise interactions, the rest are
noise.  Everything is a function of `seed`; every seed has the same
sizes.  The held-out rows are the last `n_held` (rows are i.i.d.).

    generate(seed, n_train, n_held, n_features, task=..., **args)
        -> dict(X_train, y_train, X_held, y_held)   # float32, C order
"""

from __future__ import annotations

import numpy as np


def generate(seed: int, n_train: int, n_held: int, n_features: int, *,
             task: str, n_informative: int = 20, n_interactions: int = 5,
             signal_scale: float = 10.0, noise: float = 1.0) -> dict:
    if task not in ("regression", "classification"):
        raise ValueError(f"task={task!r}: regression | classification")
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    n = int(n_train) + int(n_held)
    k = min(int(n_informative), int(n_features))
    # the model is drawn first, so it does not depend on the row count
    w = rng.uniform(0.5, 1.5, k) * rng.choice([-1.0, 1.0], k)
    pairs = rng.integers(0, k, size=(int(n_interactions), 2))
    c = rng.uniform(0.5, 1.0, int(n_interactions))
    X = rng.standard_normal((n, int(n_features)), dtype=np.float32)
    Xi = X[:, :k].astype(np.float64)
    score = Xi @ w
    for (a, b), ck in zip(pairs, c):
        score += ck * Xi[:, a] * Xi[:, b]
    score /= np.sqrt(float(np.sum(w * w) + np.sum(c * c)))   # unit variance
    eps = rng.standard_normal(n)
    if task == "regression":
        y = signal_scale * score + noise * eps
    else:
        y = (score + noise * eps > 0.0)
    y = y.astype(np.float32)
    return {"X_train": X[:n_train], "y_train": y[:n_train],
            "X_held": X[n_train:], "y_held": y[n_train:]}
