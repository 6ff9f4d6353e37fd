"""Device-idle milliseconds per round of the traced window, put down to
what the host was doing: the idle time that falls under the innermost
program span named `span` among `spans` (the program's
`jax.profiler.TraceAnnotation`s, which `Trace.host` holds), or, with
`span` null, under none of them.  Over one set of `spans` the readings
sum to the window's idle time by construction.  `None` where the trace
holds no span of that name (of any of the names, for the rest): a
program without spans reports nothing, not 0.

The two planes' clocks.  A session stamps its device plane up to 1.7 ms
behind its host plane, by an offset of its own (in the recorded trace of
`testdata/` a program launched inside `train.absorb` runs, by its
stamps, 0.8 ms before that span opens).  Unread, the offset moves a
millisecond a round between neighbouring spans, from one session to the
next.  `align` = `{"span": <the span that blocks on the device>,
"module": <part of the name of the module it waits for>}` measures it:
a block cannot return before the module it waits for has ended, so the
least `end of span - end of its module` over the window is the offset
plus the quickest wake-up, and the spans are laid that much earlier
against the device's times.  What stays unknown is the quickest wake-up
itself, which this reads as zero: it moves within the aligned span,
from its tail to its head, and not between spans.  The offset goes to
the notes as `span_clock_offset_ms`.

With `span` null, `outer` names the enclosing spans (`train.segment`,
`train.dispatch`): the notes get `unspanned_gap_ms_by_span`, the rest
divided by the innermost of those, "" = outside every program span
(the caller's own loop)."""

import tracered


def idle_intervals(tr, device=0):
    w0, w1 = tr.window
    edges = [w0] + [x for s, e in tracered.busy_intervals(tr, device)
                    for x in (s, e)] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _self_of_thread(events, out):
    """`events` of one thread, (start, end, name), nested or disjoint:
    add to out[name] what each covers less the events nested in it."""
    stack = []                            # [end, name, covered up to]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, cur = stack.pop()
            if end > cur:
                out[name].append((cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack and s > stack[-1][2]:
            out[stack[-1][1]].append((stack[-1][2], s))
        out.setdefault(name, [])
        stack.append([e, name, s])
    close(float("inf"))


def self_intervals(tr, names, shift=0.0):
    """{name: [(start, end)]} of the host events so named, laid `shift`
    seconds earlier and clipped to the window, each less the named
    events nested inside it."""
    w0, w1 = tr.window
    by_line = {}
    for s, e, name, line in tr.host:
        s, e = max(s - shift, w0), min(e - shift, w1)
        if name in names and e > s:
            by_line.setdefault(line, []).append((s, e, name))
    out = {}
    for events in by_line.values():
        _self_of_thread(events, out)
    return out


def clock_offset(tr, span, module, device=0):
    """Seconds by which the host plane runs ahead of the device plane,
    or None where no `span` wholly inside the window has a `module`
    execution under it: the least `span end - module end` (see above)."""
    w0, w1 = tr.window
    mods = [(s, e) for s, e, name in tr.modules[device] if module in name]
    found = None
    for s, e, name, _ in tr.host:
        if name != span or s < w0 or e > w1 or not mods:
            continue
        ms, me = max(mods, key=lambda m: min(m[1], e) - max(m[0], s))
        if min(me, e) > max(ms, s):
            found = e - me if found is None else min(found, e - me)
    return found


def overlap(a, b):
    """Seconds shared by two lists of disjoint intervals."""
    a, b = sorted(a), sorted(b)
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def read(ctx, *, span, spans, align=None, outer=()):
    tr, rounds = ctx.get("trace"), ctx["counts"].get("rounds_in_window")
    if tr is None or not rounds:
        return None
    shift = 0.0
    if align is not None:
        key = ("_span_clock_offset", align["span"], align["module"])
        if key not in ctx:
            ctx[key] = clock_offset(tr, align["span"], align["module"])
        if ctx[key] is not None:
            shift = ctx[key]
            ctx["notes"]["span_clock_offset_ms"] = 1e3 * shift
    own = self_intervals(tr, set(spans) | set(outer), shift)
    if not any(k in own for k in spans) or (
            span is not None and span not in own):
        return None
    idle = idle_intervals(tr)
    if span is not None:
        return 1e3 * overlap(idle, own[span]) / rounds
    under = {k: 1e3 * overlap(idle, iv) / rounds for k, iv in own.items()}
    rest = 1e3 * sum(e - s for s, e in idle) / rounds - sum(
        v for k, v in under.items() if k in spans)
    if outer:
        by = {k: under[k] for k in outer if k in under}
        by[""] = rest - sum(by.values())
        ctx["notes"]["unspanned_gap_ms_by_span"] = by
    return rest
