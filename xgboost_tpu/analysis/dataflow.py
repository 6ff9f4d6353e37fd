"""xgtpu-lint v3: dataflow-aware JAX tracing rules (ANALYSIS.md §v3).

The v1 rules are pattern matchers over one AST node at a time; the
hazards this module targets are relations BETWEEN statements — a buffer
donated at line 40 and read at line 55, a side effect inside a function
whose only callers are ``jax.jit``, a ``psum`` whose axis name never
appears in the enclosing ``shard_map``'s specs.  Two shared layers feed
three rules:

- :class:`FunctionFlow` — an intraprocedural def-use view of one
  function: every binding site (assignments, loop targets, ``with
  ... as``, walrus), every ``Name`` load, both in stable source order,
  plus param-rooted taint (a name assigned from a tainted expression is
  tainted, transitively) — reaching-definitions flattened to source
  order, which is exact for the straight-line callers this tree has
  and conservative under branches (both arms count as "after").
- :func:`traced_functions` — the set of function defs whose bodies
  execute under a JAX trace: jit-decorated (directly or via
  ``functools.partial(jax.jit, ...)``), passed to ``jax.jit`` /
  ``shard_map`` / ``lax.scan``-family wrappers by name, or nested
  inside either.

Rules (registered in rules.py alongside XGT001-XGT007):

  XGT013  use-after-donate — an argument at a ``donate_argnums``
          position of a jitted callable is DEAD after the call (XLA
          may have reused the buffer); the carry-rebind idiom
          ``carry = fn(carry, ...)`` is the blessed pattern.
  XGT014  impure traced scope — obs/metrics emission, fault
          injection, ``time.*``, ``print``/``open``, global/nonlocal
          mutation, host pulls, or ``np.asarray`` on traced values
          inside a traced function: the side effect fires once at
          trace time (or never), not per execution.
  XGT015  collective axis discipline — ``psum``/``all_gather`` axis
          names must match an axis the enclosing ``shard_map``'s
          specs/mesh mention, and collectives must not sit under
          Python branches on traced (param-tainted) values.

Like every rule here: precision over recall — an unresolvable name is
skipped, not guessed at.  The runtime twin of XGT013 is
:class:`~xgboost_tpu.analysis.runtime.DonationGuard`.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from xgboost_tpu.analysis.core import (FileContext, Finding, const_str,
                                       dotted_name, terminal_name)

FunctionNode = (ast.FunctionDef, ast.AsyncFunctionDef)


# ------------------------------------------------------------- jit helpers
def _is_jit(node: ast.AST) -> bool:
    """``jax.jit`` / bare ``jit`` (the only spellings in this tree)."""
    return (dotted_name(node) in ("jax.jit", "jit")
            or (isinstance(node, ast.Attribute) and node.attr == "jit"))


def _const_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, int)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def _kw_names(call: ast.Call, kw_name: str) -> Set[str]:
    """Constant string(s) of a keyword like ``static_argnames=``."""
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg != kw_name:
            continue
        s = const_str(kw.value)
        if s is not None:
            names.add(s)
        elif isinstance(kw.value, (ast.Tuple, ast.List)):
            for e in kw.value.elts:
                s = const_str(e)
                if s:
                    names.add(s)
    return names


def _donate_argnums(call: ast.Call) -> Optional[Tuple[int, ...]]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return _const_int_tuple(kw.value)
    return None


def _jit_call_of(node: ast.AST) -> Optional[ast.Call]:
    """The jit-configuring Call when ``node`` wraps a function in jit:
    ``jax.jit(f, ...)`` -> that call; ``functools.partial(jax.jit,
    ...)(f)`` -> the partial call (which carries the keywords)."""
    if not isinstance(node, ast.Call):
        return None
    if _is_jit(node.func):
        return node
    f = node.func
    if (isinstance(f, ast.Call) and terminal_name(f.func) == "partial"
            and f.args and _is_jit(f.args[0])):
        return f
    return None


def _wrapped_callable(node: ast.Call) -> Optional[str]:
    """The NAME being jit-wrapped by ``node`` (``jax.jit(f)`` /
    ``partial(jax.jit, ...)(f)``), when it is a plain name."""
    cfg = _jit_call_of(node)
    if cfg is None:
        return None
    if cfg is node:                       # jax.jit(f, ...)
        if node.args and isinstance(node.args[0], ast.Name):
            return node.args[0].id
        return None
    if node.args and isinstance(node.args[0], ast.Name):
        return node.args[0].id            # partial(jax.jit, ..)(f)
    return None


# ------------------------------------------------------------ traced scope
#: wrapper callables whose function-valued arguments execute under a
#: JAX trace.  ``scan``/``while_loop``/``cond`` cover the lax control
#: flow family; ``shard_map`` is matched by terminal name
#: (``jax.shard_map`` however it was imported).
_TRACING_WRAPPERS = frozenset({
    "jit", "pmap", "vmap", "shard_map", "scan", "while_loop",
    "fori_loop", "cond", "grad", "value_and_grad", "remat",
    "checkpoint", "custom_vjp", "custom_jvp"})


def traced_functions(ctx: FileContext) -> Set[ast.AST]:
    """Every FunctionDef whose body runs under a JAX trace, plus all
    function defs nested inside one.  Also records, per traced root,
    the static argnames its jit wrapping declares (``.xgtpu_static``
    attribute) so taint can skip trace-static params."""
    by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, FunctionNode):
            by_name.setdefault(node.name, []).append(node)

    roots: Dict[ast.AST, Set[str]] = {}

    def add_root(fn: ast.AST, statics: Set[str]) -> None:
        roots.setdefault(fn, set()).update(statics)

    for node in ast.walk(ctx.tree):
        if isinstance(node, FunctionNode):
            for dec in node.decorator_list:
                if _is_jit(dec):
                    add_root(node, set())
                elif isinstance(dec, ast.Call):
                    cfg = dec if _is_jit(dec.func) else _jit_call_of(dec)
                    if cfg is not None:
                        add_root(node, _kw_names(cfg, "static_argnames"))
        if not isinstance(node, ast.Call):
            continue
        cfg = _jit_call_of(node)
        if cfg is not None:
            name = _wrapped_callable(node)
            if name:
                for fn in by_name.get(name, ()):
                    add_root(fn, _kw_names(cfg, "static_argnames"))
            continue
        if terminal_name(node.func) in _TRACING_WRAPPERS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    for fn in by_name.get(arg.id, ()):
                        add_root(fn, set())

    traced: Set[ast.AST] = set()
    for fn, statics in roots.items():
        fn.xgtpu_static = statics  # type: ignore[attr-defined]
        for sub in ast.walk(fn):
            if isinstance(sub, FunctionNode):
                traced.add(sub)
    return traced


def _param_names(fn) -> Set[str]:
    a = fn.args
    names = {p.arg for p in a.posonlyargs + a.args}
    if a.vararg:
        names.add(a.vararg.arg)
    return names


def param_taint(fn) -> Set[str]:
    """Names carrying (possibly) traced values inside ``fn``: its
    positional params minus declared ``static_argnames`` (kw-only
    params are excluded wholesale — every jit wrapper in this tree
    passes statics keyword-only), closed transitively over simple
    assignments whose right-hand side reads a tainted name."""
    statics = getattr(fn, "xgtpu_static", set())
    tainted = _param_names(fn) - set(statics)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign, ast.NamedExpr)):
                continue
            value = node.value
            if value is None:
                continue
            if not any(isinstance(s, ast.Name) and s.id in tainted
                       and isinstance(s.ctx, ast.Load)
                       for s in ast.walk(value)):
                continue
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for name in _target_names(t):
                    if name not in tainted:
                        tainted.add(name)
                        changed = True
    return tainted


# ------------------------------------------------------------ FunctionFlow
def _target_names(target: ast.AST) -> Iterator[str]:
    """Every plain name bound by an assignment target."""
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, ast.Starred):
        yield from _target_names(target.value)
    elif isinstance(target, (ast.Tuple, ast.List)):
        for e in target.elts:
            yield from _target_names(e)


def stmt_bound_names(stmt: ast.AST) -> Set[str]:
    """Names (re)bound by ONE statement's own targets."""
    out: Set[str] = set()
    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            out.update(_target_names(t))
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        out.update(_target_names(stmt.target))
    elif isinstance(stmt, ast.For):
        out.update(_target_names(stmt.target))
    elif isinstance(stmt, ast.With):
        for item in stmt.items:
            if item.optional_vars is not None:
                out.update(_target_names(item.optional_vars))
    return out


class FunctionFlow:
    """Source-ordered def/use events for one function body.

    ``defs[name]`` / ``uses[name]`` are lists of ``(lineno, col,
    node)`` sorted by position.  Nested function bodies are EXCLUDED:
    a closure's reads execute at some unrelated time, and guessing
    would trade precision for noise (ANALYSIS.md §v3)."""

    def __init__(self, ctx: FileContext, fn) -> None:
        self.ctx = ctx
        self.fn = fn
        self.defs: Dict[str, List[Tuple[int, int, ast.AST]]] = {}
        self.uses: Dict[str, List[Tuple[int, int, ast.AST]]] = {}
        self.aliases: Dict[str, List[Tuple[int, str, ast.AST]]] = {}
        for node in self._walk_own(fn):
            if isinstance(node, ast.Name):
                rec = (node.lineno, node.col_offset, node)
                if isinstance(node.ctx, ast.Load):
                    self.uses.setdefault(node.id, []).append(rec)
                else:
                    self.defs.setdefault(node.id, []).append(rec)
            elif isinstance(node, ast.Assign):
                # simple alias copy: ``a = b`` (the donated-buffer
                # aliasing hazard XGT013's MUST-FAIL fixture pins)
                if isinstance(node.value, ast.Name):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            self.aliases.setdefault(
                                node.value.id, []).append(
                                    (node.lineno, t.id, node))
        for events in self.defs.values():
            events.sort(key=lambda r: (r[0], r[1]))
        for events in self.uses.values():
            events.sort(key=lambda r: (r[0], r[1]))

    @staticmethod
    def _walk_own(fn) -> Iterator[ast.AST]:
        """Walk ``fn``'s body without descending into nested function
        defs or lambdas."""
        stack: List[ast.AST] = list(fn.body)
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if isinstance(child, FunctionNode + (ast.Lambda,)):
                    continue
                stack.append(child)

    def first_event_after(self, name: str, line: int
                          ) -> Optional[Tuple[str, ast.AST]]:
        """The first def or use of ``name`` strictly after ``line`` ->
        ``("def"|"use", node)`` — the reaching-definitions question
        XGT013 asks, flattened to source order."""
        events: List[Tuple[int, int, str, ast.AST]] = []
        for ln, col, node in self.defs.get(name, ()):
            if ln > line:
                events.append((ln, col, "def", node))
        for ln, col, node in self.uses.get(name, ()):
            if ln > line:
                events.append((ln, col, "use", node))
        if not events:
            return None
        events.sort(key=lambda r: (r[0], r[1]))
        _, _, kind, node = events[0]
        return kind, node

    def live_aliases(self, name: str, line: int) -> List[str]:
        """Names that are plain copies of ``name`` made before
        ``line`` and not rebound again before it."""
        out = []
        for ln, alias, _ in self.aliases.get(name, ()):
            if ln >= line or alias == name:
                continue
            redef = [d for d, _, n in self.defs.get(alias, ())
                     if ln < d < line]
            if not redef:
                out.append(alias)
        return out


def enclosing_stmt(ctx: FileContext, node: ast.AST) -> ast.AST:
    """The nearest enclosing STATEMENT of an expression node."""
    cur = node
    while not isinstance(cur, ast.stmt):
        parent = ctx.parent(cur)
        if parent is None:
            return cur
        cur = parent
    return cur


def enclosing_function(ctx: FileContext, node: ast.AST):
    for anc in ctx.ancestors(node):
        if isinstance(anc, FunctionNode):
            return anc
    return None


# ----------------------------------------------------------------- XGT013
class Rule:
    code = "XGT000"
    name = "base"

    def applies(self, path: str) -> bool:
        return True

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        raise NotImplementedError


class UseAfterDonate(Rule):
    """XGT013: a caller reads an argument it passed at a
    ``donate_argnums`` position of a jitted callable, after the call —
    XLA may already have reused (or on CPU will warn and copy) that
    buffer, and on TPU the read returns garbage or raises.  The
    blessed idiom is the carry rebind, ``margin, ... = fn(margin,
    ...)``: the donated name is rebound by the call's own statement,
    so nothing can read the dead buffer.  Donation maps follow simple
    aliases, including the conditional-wrapper selection
    ``fn = donated if donate else plain`` (union of positions), and
    ``tuple(name)`` wrapping of a donated pytree argument.  A donating
    call inside a loop that does NOT rebind its donated argument is
    flagged outright: iteration 2 passes an already-donated buffer."""

    code = "XGT013"
    name = "use-after-donate"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        donated = self._module_donation_map(ctx)
        for node in ast.walk(ctx.tree):
            if isinstance(node, FunctionNode):
                yield from self._check_function(ctx, node, donated)

    # -------------------------------------------------- donation maps
    @staticmethod
    def _module_donation_map(ctx: FileContext
                             ) -> Dict[str, FrozenSet[int]]:
        out: Dict[str, FrozenSet[int]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, FunctionNode):
                for dec in node.decorator_list:
                    cfg = (dec if isinstance(dec, ast.Call)
                           and _is_jit(dec.func) else _jit_call_of(dec))
                    if cfg is None:
                        continue
                    nums = _donate_argnums(cfg)
                    if nums:
                        out[node.name] = frozenset(nums)
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            cfg = _jit_call_of(node.value)
            if cfg is None:
                continue
            nums = _donate_argnums(cfg)
            if nums:
                out[node.targets[0].id] = frozenset(nums)
        return out

    @staticmethod
    def _local_donation_map(fn, donated: Dict[str, FrozenSet[int]]
                            ) -> Dict[str, FrozenSet[int]]:
        """Extend the module map with function-local aliases:
        ``scan = _donated`` and ``scan = _donated if c else _plain``
        (union of referenced donated names' positions)."""
        local = dict(donated)
        changed = True
        while changed:
            changed = False
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    continue
                value = node.value
                names: List[str] = []
                if isinstance(value, ast.Name):
                    names = [value.id]
                elif isinstance(value, ast.IfExp):
                    names = [n.id for n in (value.body, value.orelse)
                             if isinstance(n, ast.Name)]
                positions: Set[int] = set()
                for n in names:
                    positions.update(local.get(n, ()))
                if positions:
                    tgt = node.targets[0].id
                    if frozenset(positions) != local.get(tgt):
                        local[tgt] = frozenset(positions)
                        changed = True
        return local

    @staticmethod
    def _donated_arg_names(call: ast.Call,
                           positions: FrozenSet[int]) -> List[str]:
        """Caller-side names whose buffers the call donates: a bare
        ``name`` or ``tuple(name)`` at a donated position."""
        out = []
        for i in sorted(positions):
            if i >= len(call.args):
                continue
            arg = call.args[i]
            if (isinstance(arg, ast.Call)
                    and terminal_name(arg.func) == "tuple" and arg.args):
                arg = arg.args[0]
            if isinstance(arg, ast.Name):
                out.append(arg.id)
        return out

    # ------------------------------------------------------- checking
    def _check_function(self, ctx: FileContext, fn,
                        donated: Dict[str, FrozenSet[int]]
                        ) -> Iterator[Finding]:
        local = self._local_donation_map(fn, donated)
        calls = []
        for node in FunctionFlow._walk_own(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in local):
                calls.append(node)
        if not calls:
            return
        flow = FunctionFlow(ctx, fn)
        for call in calls:
            stmt = enclosing_stmt(ctx, call)
            rebound = stmt_bound_names(stmt)
            positions = local[call.func.id]
            for name in self._donated_arg_names(call, positions):
                in_loop = self._loop_between(ctx, call, fn)
                if name not in rebound and in_loop is not None:
                    yield ctx.finding(
                        self.code, call,
                        f"{call.func.id}() donates {name!r} but the "
                        "enclosing loop never rebinds it — iteration 2 "
                        "passes an already-donated buffer; use the "
                        f"carry rebind ({name} = "
                        f"{call.func.id}({name}, ...))")
                    continue
                # a carry rebind revives the NAME, but any pre-call
                # alias still points at the dead buffer — check those
                # regardless
                dead_names = flow.live_aliases(name, call.lineno)
                if name not in rebound:
                    dead_names = [name] + dead_names
                end = getattr(stmt, "end_lineno", stmt.lineno)
                for dead in dead_names:
                    nxt = flow.first_event_after(dead, end)
                    if nxt is None or nxt[0] == "def":
                        continue
                    _, use = nxt
                    what = (f"{dead!r} (aliasing donated {name!r})"
                            if dead != name else f"{name!r}")
                    yield ctx.finding(
                        self.code, use,
                        f"use-after-donate: {what} was donated to "
                        f"{call.func.id}() on line {call.lineno} "
                        "(donate_argnums) and is read here — the "
                        "buffer may already be reused; rebind the "
                        "result over the donated name (carry rebind) "
                        "or drop the read")

    @staticmethod
    def _loop_between(ctx: FileContext, node: ast.AST, fn):
        for anc in ctx.ancestors(node):
            if anc is fn:
                return None
            if isinstance(anc, (ast.For, ast.While)):
                return anc
            if isinstance(anc, FunctionNode + (ast.Lambda,)):
                return None
        return None


# ----------------------------------------------------------------- XGT014
#: call terminal names that are side effects when traced: obs event /
#: metric emission, fault injection, console/file I/O.  ``jax.debug.*``
#: is the sanctioned escape hatch and is exempted by dotted prefix.
_IMPURE_TERMINALS = frozenset({
    "event", "_event", "emit", "span", "inject", "print", "open"})
#: host pulls: force a device sync (and break under trace)
_HOST_PULL_DOTTED = frozenset({"jax.device_get", "device_get"})
_NP_CAST_DOTTED = frozenset({"np.asarray", "np.array",
                             "numpy.asarray", "numpy.array"})


class ImpureTracedScope(Rule):
    """XGT014: a side effect inside a function that executes under a
    JAX trace (jit-decorated, passed to jit/shard_map/lax.scan, or
    nested in one).  Traced Python runs ONCE at trace time: an obs
    ``event()``/``span()``, ``faults.inject()``, ``time.*`` read,
    ``print``/``open``, or global/nonlocal mutation fires once per
    compile — not per execution — which is exactly the silent
    obs-vs-XLA divergence the ``XGBTPU_OBS_PHASES=0`` fallback existed
    to dodge; ``np.asarray`` on a traced value raises a
    TracerArrayConversionError at best.  Hoist the side effect to the
    host-side caller (the mock.collective replay in do_boost_fused is
    the worked example), or use ``jax.debug.*`` (exempt)."""

    code = "XGT014"
    name = "impure-traced-scope"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        traced = traced_functions(ctx)
        if not traced:
            return
        taint_cache: Dict[ast.AST, Set[str]] = {}
        for fn in traced:
            for node in FunctionFlow._walk_own(fn):
                if isinstance(node, (ast.Global, ast.Nonlocal)):
                    kind = ("global" if isinstance(node, ast.Global)
                            else "nonlocal")
                    yield ctx.finding(
                        self.code, node,
                        f"{kind} mutation inside traced {fn.name}(): "
                        "runs once at trace time, not per execution — "
                        "thread state through the carry instead")
                if not isinstance(node, ast.Call):
                    continue
                msg = self._impure_call(ctx, fn, node, taint_cache)
                if msg:
                    yield ctx.finding(
                        self.code, node,
                        f"{msg} inside traced {fn.name}(): traced "
                        "Python runs once at trace time (or breaks the "
                        "trace) — hoist it to the host-side caller, or "
                        "route through jax.debug.* if it must observe "
                        "traced values")

    def _impure_call(self, ctx: FileContext, fn, node: ast.Call,
                     taint_cache: Dict[ast.AST, Set[str]]
                     ) -> Optional[str]:
        d = dotted_name(node.func)
        if d is not None and d.startswith("jax.debug."):
            return None
        t = terminal_name(node.func)
        if t in _IMPURE_TERMINALS:
            return f"side-effect call {t}()"
        if d is not None and d.startswith("time."):
            return f"wall-clock read {d}()"
        if d in _HOST_PULL_DOTTED:
            return f"host pull {d}()"
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "item" and not node.args):
            return "host pull .item()"
        if d in _NP_CAST_DOTTED and node.args:
            tainted = taint_cache.setdefault(fn, param_taint(fn))
            if any(isinstance(s, ast.Name) and s.id in tainted
                   and isinstance(s.ctx, ast.Load)
                   for s in ast.walk(node.args[0])):
                return f"numpy cast {d}() of a traced value"
        return None


# ----------------------------------------------------------------- XGT015
_COLLECTIVE_TERMINALS = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "psum_scatter", "axis_index"})
#: attribute reads of a traced name that are trace-STATIC (shape
#: metadata), so branching on them is fine
_STATIC_ATTRS = frozenset({"shape", "ndim", "size", "dtype"})
_STATIC_TEST_CALLS = frozenset({"isinstance", "len", "getattr",
                                "hasattr", "callable"})


def _axis_token(node: ast.AST, consts: Dict[str, str],
                params: Set[str]) -> Optional[str]:
    """Canonical token of an axis-name expression: a resolved string,
    ``$NAME`` for an unresolved (e.g. imported) constant, or None for
    a function parameter / unresolvable expression (config seams are
    skipped, not guessed)."""
    s = const_str(node)
    if s is not None:
        return s
    if isinstance(node, ast.Name):
        if node.id in params:
            return None
        if node.id in consts:
            return consts[node.id]
        return "$" + node.id
    return None


class CollectiveAxisDiscipline(Rule):
    """XGT015: dataflow-powered deepening of XGT007 for ``shard_map``
    programs.

    (a) axis match — a collective lexically inside a function passed
        to ``shard_map`` must name an axis the call site's
        ``P(...)``/``PartitionSpec(...)`` specs (or an in-file mesh
        construction) mention.  Names resolve through in-file
        constants (``DATA_AXIS = "data"``); imported axis constants
        match symbolically (the same NAME on both sides), so a psum
        over a renamed or misspelled axis is a finding while the
        repo's ``DATA_AXIS`` convention passes.
    (b) data-dependent branch — a collective under an ``if``/``while``
        whose test reads a param-tainted (traced) value dynamically:
        the branch is resolved ONCE at trace time, so ranks disagreeing
        at runtime would skip the collective and deadlock the mesh.
        ``is None`` tests, ``isinstance``, and ``.shape``/``.ndim``
        reads are trace-static and exempt.
    """

    code = "XGT015"
    name = "collective-axis-discipline"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        consts = {
            t.id: node.value.value
            for node in ctx.tree.body
            if isinstance(node, ast.Assign) and len(node.targets) == 1
            and isinstance((t := node.targets[0]), ast.Name)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)}
        yield from self._check_axis_match(ctx, consts)
        yield from self._check_data_branches(ctx)

    # ------------------------------------------------- (a) axis match
    def _check_axis_match(self, ctx: FileContext,
                          consts: Dict[str, str]) -> Iterator[Finding]:
        by_name: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, FunctionNode):
                by_name.setdefault(node.name, []).append(node)
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and terminal_name(node.func) == "shard_map"
                    and node.args):
                continue
            inner = node.args[0]
            fns = (by_name.get(inner.id, ())
                   if isinstance(inner, ast.Name) else ())
            if not fns:
                continue
            axes = self._site_axes(ctx, node, consts)
            if not axes:
                continue
            for fn in fns:
                params = _param_names(fn) | {
                    a.arg for a in fn.args.kwonlyargs}
                for sub in ast.walk(fn):
                    if not (isinstance(sub, ast.Call) and
                            terminal_name(sub.func)
                            in _COLLECTIVE_TERMINALS):
                        continue
                    tok = self._collective_axis(sub, consts, params)
                    if tok is not None and tok not in axes:
                        pretty = tok.lstrip("$")
                        yield ctx.finding(
                            self.code, sub,
                            f"collective {terminal_name(sub.func)}() "
                            f"names axis {pretty!r}, but the enclosing "
                            "shard_map's specs/mesh mention only "
                            f"{sorted(a.lstrip('$') for a in axes)} — "
                            "a renamed or misspelled mesh axis fails "
                            "at trace time on device but passes "
                            "single-host tests")

    def _site_axes(self, ctx: FileContext, call: ast.Call,
                   consts: Dict[str, str]) -> Set[str]:
        """Axis tokens the shard_map call site declares: P()/
        PartitionSpec() arguments reachable from the call's specs
        (following simple local assignments like ``D = P(DATA_AXIS)``)
        plus axis names of in-file mesh constructions."""
        axes: Set[str] = set()
        scope = enclosing_function(ctx, call) or ctx.tree
        for node in ast.walk(scope):
            if (isinstance(node, ast.Call)
                    and terminal_name(node.func) in ("P", "PartitionSpec")):
                for arg in node.args:
                    tok = _axis_token(arg, consts, set())
                    if tok:
                        axes.add(tok)
            if (isinstance(node, ast.Call)
                    and terminal_name(node.func) in ("Mesh", "make_mesh",
                                                     "AbstractMesh")):
                for arg in list(node.args) + [
                        kw.value for kw in node.keywords]:
                    if isinstance(arg, (ast.Tuple, ast.List)):
                        for e in arg.elts:
                            tok = _axis_token(e, consts, set())
                            if tok:
                                axes.add(tok)
        return axes

    @staticmethod
    def _collective_axis(call: ast.Call, consts: Dict[str, str],
                         params: Set[str]) -> Optional[str]:
        axis_expr = None
        for kw in call.keywords:
            if kw.arg == "axis_name":
                axis_expr = kw.value
        if axis_expr is None and len(call.args) >= 2:
            axis_expr = call.args[1]
        if axis_expr is None:
            return None
        return _axis_token(axis_expr, consts, params)

    # ----------------------------------------- (b) data-dependent ifs
    def _check_data_branches(self, ctx: FileContext) -> Iterator[Finding]:
        traced = traced_functions(ctx)
        taint_cache: Dict[ast.AST, Set[str]] = {}
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and terminal_name(node.func)
                    in _COLLECTIVE_TERMINALS):
                continue
            fn = enclosing_function(ctx, node)
            if fn is None or fn not in traced:
                continue
            tainted = taint_cache.setdefault(fn, param_taint(fn))
            for anc in ctx.ancestors(node):
                if anc is fn or isinstance(anc, FunctionNode):
                    break
                if not isinstance(anc, (ast.If, ast.While, ast.IfExp)):
                    continue
                ref = self._dynamic_tainted_ref(ctx, anc.test, tainted)
                if ref:
                    yield ctx.finding(
                        self.code, node,
                        f"collective {terminal_name(node.func)}() "
                        "under a Python branch on traced value "
                        f"{ref!r}: the branch resolves once at trace "
                        "time — shards disagreeing at runtime would "
                        "skip the collective and deadlock; use "
                        "jnp.where / lax.cond, or branch on static "
                        "config")
                    break

    @staticmethod
    def _dynamic_tainted_ref(ctx: FileContext, test: ast.AST,
                             tainted: Set[str]) -> Optional[str]:
        for sub in ast.walk(test):
            if not (isinstance(sub, ast.Name) and sub.id in tainted
                    and isinstance(sub.ctx, ast.Load)):
                continue
            parent = ctx.parent(sub)
            if (isinstance(parent, ast.Attribute)
                    and parent.attr in _STATIC_ATTRS):
                continue
            if (isinstance(parent, ast.Call)
                    and terminal_name(parent.func) in _STATIC_TEST_CALLS):
                continue
            if (isinstance(parent, ast.Compare)
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in parent.ops)):
                continue
            return sub.id
        return None
