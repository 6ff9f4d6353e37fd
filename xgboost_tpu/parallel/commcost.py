"""Collective-cost accounting for distributed training (VERDICT r3
item 2).

The network boundary of data-parallel tree growth is the per-depth
histogram allreduce — the role of the reference's
``histred.Allreduce`` (``updater_histmaker-inl.hpp:343-346``), whose
payload is TStats x bins x features x nodes.  Here the same payload is
``n_node x F x B x 2`` f32 per level, psum-reduced over the mesh's
data axis (``parallel/dp.py``).

This module makes that cost a NUMBER instead of prose:

  - :func:`hist_psum_bytes` — the analytic per-level/total payload;
  - :func:`hlo_collectives` — the collectives ACTUALLY present in a
    compiled XLA program, with their payload bytes (what the
    regression test pins against the analytic model);
  - :func:`project_round_time` — a compute/communication model for a
    k-chip mesh (a projection, not a measurement).
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}

# one collective op; shapes like f32[32,28,64,2].  The result type is
# everything between '=' and the opcode TOKEN (which is immediately
# followed by '('): anchoring on the paren keeps operand names like
# '%all-reduce.3' inside the operand list from matching as the opcode,
# and a strict result-type group keeps operand shapes out of the
# payload (both bugs a looser regex exhibited — caught in review).
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*"
    r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[0-9,]*\})?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute)"
    r"(-start)?\(")


def _shapes_in(shape_list: str) -> List[Tuple[str, str]]:
    return _SHAPE_RE.findall(shape_list)


def _one_shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 0)


def hlo_collectives(hlo_text: str) -> List[Tuple[str, str, int]]:
    """[(op, shape, payload_bytes)] for every collective in an HLO
    dump (``jax.jit(f).lower(...).compile().as_text()``).

    Async pairs: a ``-start`` tuple result holds (operand-alias,
    produced buffer[, u32[] context scalars...]); context scalars are
    dropped, then the payload is the produced buffer: the LARGEST
    remaining element for all-reduce / collective-permute /
    all-gather, but the SMALLEST for reduce-scatter (its result is
    1/n_shards of the operand).  ``-done`` ops carry none."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        shapes, op, start = m.group(1), m.group(2), m.group(3)
        parsed = _shapes_in(shapes)
        if not parsed:
            continue
        sizes = [_one_shape_bytes(t, d) for t, d in parsed]
        if start and shapes.startswith("("):
            real = [s for s in sizes if s > 8] or sizes
            payload = min(real) if op == "reduce-scatter" else max(real)
        else:
            payload = sum(sizes)
        out.append((op, shapes.strip(), payload))
    return out


def hist_psum_bytes(max_depth: int, n_feat: int, n_bin: int,
                    stat_bytes: int = 8) -> Dict[int, int]:
    """Analytic per-level histogram-psum payload: ``2**d * F * B *
    stat_bytes`` (the (G, H) f32 pair = 8 bytes), for non-terminal
    levels d = 0..max_depth-1.  Matches the f32[n,F,B,2] all-reduce
    shapes the compiled program carries (test_distributed pins this)."""
    return {d: (1 << d) * n_feat * n_bin * stat_bytes
            for d in range(max_depth)}


_ROUND_MODEL_CACHE: Optional[tuple] = None  # (mtime_ns or None, model)


def fitted_round_model() -> Optional[dict]:
    """The measured compute model from ``ROUND_MODEL.json`` (written by
    ``tools/fit_round_model.py`` from a single-chip row sweep at the
    bench config), or None if no fit has been recorded.  Fields:
    ``fixed_round_s`` (per-round launch/levels overhead — the
    row-count-independent intercept) and ``per_row_s`` (the slope).
    Cached by file mtime: auto rounds-per-dispatch sizing consults this
    on EVERY fused segment plan (64 tenant lanes ask 64 times a cycle),
    and a json parse per ask is measurable host overhead."""
    global _ROUND_MODEL_CACHE
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ROUND_MODEL.json")
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        mtime = None
    if _ROUND_MODEL_CACHE is not None and _ROUND_MODEL_CACHE[0] == mtime:
        return _ROUND_MODEL_CACHE[1]
    if mtime is None:
        _ROUND_MODEL_CACHE = (None, None)
        return None
    try:
        with open(path) as f:
            m = json.load(f)
        float(m["fixed_round_s"]), float(m["per_row_s"])
        _ROUND_MODEL_CACHE = (mtime, m)
        return m
    except Exception as e:
        # a torn/hand-edited fit file falls back to the analytic model;
        # counted so a projection silently ignoring the fit is visible
        from xgboost_tpu.obs.metrics import swallowed_error
        swallowed_error("parallel.commcost.round_model", e)
        return None


def project_round_time(rows: int, max_depth: int, n_feat: int,
                       n_bin: int, n_chips: int,
                       single_chip_round_s: float,
                       single_chip_rows: int,
                       ici_allreduce_bw: float = 1e11,
                       fixed_round_s: Optional[float] = None,
                       per_row_s: Optional[float] = None
                       ) -> Dict[str, float]:
    """Projected per-round time on a k-chip mesh.

    Model: compute = ``fixed + per_row * rows/chip`` — a fixed per-round
    launch/levels overhead plus a row-proportional term; the psum adds
    ring-allreduce time ``2 * bytes * (k-1)/k / bw`` per level (the
    levels synchronize, so comm does NOT overlap compute here — a
    conservative model).  ``ici_allreduce_bw`` defaults to 1e11 B/s
    per chip — the order of the public v5e ICI figure (4 links x ~25
    GB/s/direction on the 2D torus); it enters only the psum term,
    which is microseconds at these payloads, so the projection is
    insensitive to it.

    ``fixed_round_s`` / ``per_row_s`` default to the MEASURED fit in
    ``ROUND_MODEL.json`` (single-chip row sweep at the bench config —
    tools/fit_round_model.py; round 5, replacing round 4's assumed
    4 ms intercept).  With no fit on disk, the intercept falls back to
    that historical assumption and the slope is derived from the
    caller's measured single-chip point, so callers always pass the
    anchor (single_chip_round_s, single_chip_rows): it cross-checks
    the fit and carries the fallback.
    """
    model = fitted_round_model()
    if fixed_round_s is None:
        fixed_round_s = model["fixed_round_s"] if model else 0.004
    if per_row_s is None:
        per_row_s = (model["per_row_s"] if model
                     else max(single_chip_round_s - fixed_round_s, 0.0)
                     / single_chip_rows)
    compute = fixed_round_s + per_row_s * (rows / n_chips)
    total_bytes = sum(hist_psum_bytes(max_depth, n_feat, n_bin).values())
    comm = (2.0 * total_bytes * (n_chips - 1) / n_chips
            / ici_allreduce_bw) if n_chips > 1 else 0.0
    return {"compute_s": compute, "psum_s": comm,
            "round_s": compute + comm,
            "rounds_per_sec": 1.0 / (compute + comm),
            "psum_bytes_per_round": float(total_bytes),
            "fixed_round_s": float(fixed_round_s),
            "per_row_s": float(per_row_s),
            "fitted": bool(model)}
