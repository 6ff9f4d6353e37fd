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
    regression test pins against the analytic model).
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

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
