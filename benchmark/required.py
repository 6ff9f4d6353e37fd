"""What the algorithm requires, from shapes alone, whatever implements
it: operations and bytes of the level histograms of one tree and of one
whole boosting round, and the least time a chip with given peaks could
take for them.  A later PR cannot change these: they are the yardstick
`hist_roofline` and `round_mfu` stand on.

Counted per level histogram over N rows, F features, B bins, M nodes:
every row adds its (g, h) into one bin of every feature: 2*N*F adds;
it has to read each bin id once (1 byte for B <= 256, else 2), each
row's g, h (8 bytes) and node (4 bytes), and write M*F*B*2 float32
sums.  A round of depth D builds D level histograms (the last level's
sums follow from its parents' splits), and besides reads margin and
label and writes g, h (16 bytes and ~6 operations a row), routes every
row once per level (1 bin id + 4 + 4 bytes, ~4 operations), adds the
leaf value to the margin (8 bytes, 1 operation), and walks the new tree
over the held-out rows (D bin ids + 8 bytes, ~4*D operations a row).
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def level_histogram(N: int, F: int, B: int, M: int) -> dict:
    bin_bytes = 1 if B <= 256 else 2
    return {"ops": 2.0 * N * F,
            "bytes": N * F * bin_bytes + N * 12.0 + M * F * B * 8.0}


def tree_histograms(N: int, F: int, B: int, depth: int) -> dict:
    levels = [level_histogram(N, F, B, 1 << d) for d in range(depth)]
    return {k: sum(lv[k] for lv in levels) for k in ("ops", "bytes")}


def boosting_round(N: int, F: int, B: int, depth: int, n_held: int = 0) -> dict:
    bin_bytes = 1 if B <= 256 else 2
    hist = tree_histograms(N, F, B, depth)
    return {"ops": hist["ops"] + N * (6.0 + 4.0 * depth + 1.0)
            + n_held * 4.0 * depth,
            "bytes": hist["bytes"] + N * (16.0 + depth * (bin_bytes + 8.0) + 8.0)
            + n_held * (depth * bin_bytes + 8.0)}


def least_seconds(work: dict, peak: dict, ops_peak: str = "bf16_flops_per_s"):
    """(seconds, which bound) for `work` on a chip with `peak`."""
    t_ops = work["ops"] / peak[ops_peak]
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "hbm")
