"""Reduction of a JAX profiler trace (`*.xplane.pb`) to what the
per-layer readers and the `breakdown` need.  Reads the file with
`jax.profiler.ProfileData` and nothing else.

What the trace of a TPU holds (looked at by hand, PR 26's recorded trace
in `testdata/`): one plane `/device:TPU:<i>` per chip with the lines
`XLA Modules` (one event per executed program, named
`jit_<fn>(<fingerprint>)`) and `XLA Ops` (one event per HLO op, named by
its HLO text; a Pallas kernel is a `custom-call` with
`custom_call_target="tpu_custom_call"`; `while` / `conditional` / `call`
events span their bodies and are containers, not work), and one plane
`/host:CPU` whose `python` line holds the Python frames
(`$file.py:line fn`) and whose other lines hold runtime threads.  All
times are nanoseconds on one clock.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import NamedTuple

_CONTAINER = re.compile(r"^%[\w.\-]+ = .*? (while|conditional|call)\(")
_WINDOW_MARK = "bench_window"


class Op(NamedTuple):
    start: float     # seconds
    end: float
    name: str        # HLO text
    kernel: bool     # a Pallas / Mosaic custom call


class Trace(NamedTuple):
    window: tuple            # (start, end) seconds
    marked: bool             # the window came from the harness's mark
    ops: list                # per device: list[Op], containers dropped, clipped
    modules: list            # per device: list[(start, end, name)]
    host: list               # (start, end, name, line) of host events in the window


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    dev_ops, dev_mods, host, mark = [], [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        if _CONTAINER.match(e.name):
                            continue
                        s = e.start_ns * 1e-9
                        ops.append(Op(s, s + e.duration_ns * 1e-9, e.name,
                                      "tpu_custom_call" in e.name))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        mods.append((s, s + e.duration_ns * 1e-9, e.name))
            if ops or mods:
                dev_ops.append(sorted(ops))
                dev_mods.append(sorted(mods))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    if e.name == _WINDOW_MARK:
                        mark = (s, s + e.duration_ns * 1e-9)
                    host.append((s, s + e.duration_ns * 1e-9, e.name,
                                 line.name))
    if mark is None:
        every = [o for ops in dev_ops for o in ops]
        if not every:
            raise ValueError("no device operation in the trace")
        window = (min(o.start for o in every), max(o.end for o in every))
    else:
        window = mark
    w0, w1 = window
    clipped = [[o._replace(start=max(o.start, w0), end=min(o.end, w1))
                for o in ops if o.end > w0 and o.start < w1]
               for ops in dev_ops]
    mods = [[m for m in ms if m[1] > w0 and m[0] < w1] for ms in dev_mods]
    host = [h for h in host if h[1] > w0 and h[0] < w1
            and h[2] != _WINDOW_MARK]
    return Trace(window, mark is not None, clipped, mods, host)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(tr: Trace, device: int = 0):
    return _union((o.start, o.end) for o in tr.ops[device])


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = [sum(e - s for s, e in busy_intervals(tr, d))
           for d in range(len(tr.ops))]
    return sum(per) / max(len(per), 1)


def window_s(tr: Trace) -> float:
    return tr.window[1] - tr.window[0]


def module_events(tr: Trace, name_part: str, device: int = 0):
    return [m for m in tr.modules[device] if name_part in m[2]]


def op_seconds(tr: Trace, *, inside: str = "", kernels=None,
               device: int = 0) -> float:
    """Device seconds of ops, optionally only those that ran inside a
    module whose name holds `inside`, and only kernels / only the rest."""
    spans = ([(s, e) for s, e, _ in module_events(tr, inside, device)]
             if inside else None)
    total, i = 0.0, 0
    for o in tr.ops[device]:
        if kernels is not None and o.kernel != kernels:
            continue
        if spans is not None:
            while i < len(spans) and spans[i][1] <= o.start:
                i += 1
            if i == len(spans) or spans[i][0] > o.start:
                continue
        total += o.end - o.start
    return total


def short_name(hlo: str) -> str:
    """`%grow_tree.53 s32[1,26624,64] [pallas]` from the HLO text."""
    m = re.match(r"^(%[\w.\-]+) = \(?([\w]+\[[\d,]*\])?", hlo)
    if not m:
        return hlo[:80]
    out = m.group(1) + (" " + m.group(2) if m.group(2) else "")
    return out + (" [pallas]" if "tpu_custom_call" in hlo else "")


def top_device_ops(tr: Trace, k: int = 10, device: int = 0):
    tot = collections.Counter()
    for o in tr.ops[device]:
        tot[short_name(o.name)] += o.end - o.start
    return [[n, s] for n, s in tot.most_common(k)]


def idle_gaps(tr: Trace, k: int = 10, device: int = 0, min_gap: float = 20e-6):
    """The idle time of the window, summed by what the host was doing:
    for each gap, the innermost host event that covers most of it
    (Python frames first, then runtime threads)."""
    import numpy as np
    w0, w1 = tr.window
    busy = busy_intervals(tr, device)
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= min_gap]
    pools = []
    for want_python in (True, False):
        ev = [h for h in tr.host if (h[3] == "python") == want_python
              and h[1] - h[0] >= 0.5 * min_gap]
        pools.append((np.array([h[0] for h in ev]), np.array([h[1] for h in ev]),
                      [h[2] for h in ev]))
    tot = collections.Counter()
    for gs, ge in gaps:
        name = "(no host event)"
        for starts, ends, names in pools:
            if not names:
                continue
            cover = (np.minimum(ends, ge) - np.maximum(starts, gs)
                     >= 0.5 * (ge - gs))
            if cover.any():
                idx = np.flatnonzero(cover)
                name = names[idx[np.argmin((ends - starts)[idx])]]
                break
        tot[name] += ge - gs
    return [[n, s] for n, s in tot.most_common(k)]
