"""Device milliseconds per round of the ops, inside the modules named
`inside`, whose innermost `jax.named_scope` is `scope`: `kernels` true =
the Pallas custom calls, false = every other op, absent = all.

A trace event carries the op's HLO text and no scope.  The scope comes
from the compiled module that ran: `op_name="jit(f)/while/body/
grow.split/..."` in its instructions' metadata, looked up by instruction
name.  The map is `ctx["scope_map"]` where the caller brings one (a
recorded trace), else it is read off the process's live executables.
A scope is a dotted path component (`grow.split`); the innermost one
counts.  The first call also puts the whole division into the notes:
`xla_ms_by_scope` and `kernel_ms_by_scope`, ops of no scope under "".
`None` where no op of the scope ran, or where the module has no scopes
at all (a program without them)."""

import re

import tracered

_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*metadata=\{[^}]*"
                    r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|[/(])([a-z_]+\.[a-z_]+)(?=[/)]|$)")
_NAME = re.compile(r"^(%[\w.\-]+) = ")


def scope_map(inside):
    """{instruction name: op_name} of the live compiled modules whose
    name holds `inside`."""
    import jax.extend
    out = {}
    for ex in jax.extend.backend.get_backend().live_executables():
        try:
            mods = [m for m in ex.hlo_modules() if inside in m.name]
        except Exception:               # an executable with no module
            continue
        for m in mods:
            for line in m.to_string().splitlines():
                hit = _INSTR.match(line)
                if hit:
                    out[hit.group(1)] = hit.group(2)
    return out


def innermost(op_name):
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else ""


def by_scope(ctx, inside):
    """{(scope, is kernel): device seconds} of the ops inside `inside`."""
    key = ("_scope_seconds", inside)
    if key not in ctx:
        tr = ctx["trace"]
        names = ctx.get("scope_map")
        if names is None:
            names = scope_map(inside)
        groups = {}
        for o in tr.ops[0]:
            hit = _NAME.match(o.name)
            scope = innermost(names.get(hit.group(1))) if hit else ""
            groups.setdefault((scope, o.kernel), []).append(o)
        ctx[key] = {
            g: tracered.op_seconds(tr._replace(ops=[ops]), inside=inside)
            for g, ops in groups.items()}
    return ctx[key]


def read(ctx, *, inside, scope, kernels=None):
    tr, rounds = ctx.get("trace"), ctx["counts"].get("rounds_in_window")
    if tr is None or not rounds:
        return None
    secs = by_scope(ctx, inside)
    if not any(s for s, _ in secs):
        return None                     # no scope anywhere: nothing to read
    for kind, note in ((False, "xla_ms_by_scope"),
                       (True, "kernel_ms_by_scope")):
        ctx["notes"].setdefault(note, {
            s: 1e3 * v / rounds for (s, k), v in sorted(secs.items())
            if k == kind and v > 0})
    took = sum(v for (s, k), v in secs.items()
               if s == scope and kernels in (None, k))
    return 1e3 * took / rounds if took > 0 else None
