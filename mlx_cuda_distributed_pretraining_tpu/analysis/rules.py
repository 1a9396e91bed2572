"""graftlint rules: the JAX-specific hazards this repo keeps hitting.

Every rule is pure-AST (no jax import) and errs toward silence: a rule
that cannot *prove* the hazard from module-local source stays quiet —
``JitSpec.unknown`` (non-constant static/donate specs), cross-module
wrapping it cannot see, and shadowed names all disarm the check. The
tier-1 gate runs these over the whole package, so a chatty rule would
cost more than it catches.

Rule IDs (stable — used in suppressions and the baseline):

- ``recompile-hazard``    Python control flow on traced jit params; and
                          non-hashable literals passed for static args.
- ``rng-reuse``           a PRNG key consumed twice (or per loop
                          iteration) without split/fold_in.
- ``host-sync-in-hot-loop`` float()/.item()/np.asarray/device_get/
                          block_until_ready running unconditionally in a
                          loop that dispatches a jitted step.
- ``use-after-donate``    reading an argument after passing it at a
                          donate_argnums position.
- ``tracer-leak``         assigning traced values to self.*/globals
                          inside a jitted function.
- ``jit-in-loop``         jax.jit called inside a loop body.
- ``time-in-jit``         wall-clock reads / sleep / print / open inside
                          a jitted function body (trace-time constants).
- ``legacy-shard-map-import`` importing the deprecated
                          ``jax.experimental.shard_map`` (the package
                          calls ``jax.shard_map``).
- ``monotonic-clock``     a duration computed by subtracting two
                          ``time.time()`` readings — wall clocks step
                          under NTP; use time.monotonic()/perf_counter().
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import (
    Finding,
    ModuleContext,
    Rule,
    dotted_name,
    jit_spec_of_call,
    register,
)

# -- shared AST helpers -----------------------------------------------------


def _build_parents(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _enclosing_function(node: ast.AST, parents: Dict[ast.AST, ast.AST]) -> str:
    while node in parents:
        node = parents[node]
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return node.name
    return "<module>"


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)] \
        + [p.arg for p in a.kwonlyargs]


def _walk_skip_defs(node: ast.AST, *, skip_root_check: bool = True
                    ) -> Iterable[ast.AST]:
    """ast.walk that does not descend into nested function/lambda bodies
    (their code does not run as part of the enclosing statement flow).
    Decorator and default-argument expressions of a skipped def DO run in
    the enclosing flow (a ``@jax.jit`` decorator inside a loop compiles a
    fresh wrapper per iteration), so those are still visited."""
    stack = [node]
    first = True
    while stack:
        n = stack.pop()
        if not first and isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            if not isinstance(n, ast.Lambda):
                stack.extend(n.decorator_list)
                stack.extend(d for d in (*n.args.defaults,
                                         *n.args.kw_defaults) if d)
            continue
        first = False
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _assigned_names(node: ast.AST) -> Set[str]:
    """Dotted names bound anywhere under ``node`` (excluding nested defs):
    Assign/AugAssign/AnnAssign targets, for-targets, with-as, walrus."""
    out: Set[str] = set()

    def add_target(t: ast.AST) -> None:
        if isinstance(t, (ast.Tuple, ast.List)):
            for elt in t.elts:
                add_target(elt)
        elif isinstance(t, ast.Starred):
            add_target(t.value)
        else:
            name = dotted_name(t)
            if name:
                out.add(name)

    for n in _walk_skip_defs(node):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                add_target(t)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign, ast.NamedExpr)):
            add_target(n.target)
        elif isinstance(n, ast.For):
            add_target(n.target)
        elif isinstance(n, ast.withitem) and n.optional_vars is not None:
            add_target(n.optional_vars)
    return out


# -- module-local call graph ------------------------------------------------
#
# Hot-context rules (host-sync-in-hot-loop, time-in-jit) must not stop at
# a function boundary: a step loop that calls ``self._log(metrics)`` pays
# the float() inside _log every iteration exactly as if it were inline.
# The resolution is deliberately conservative — only calls whose terminal
# identifier names exactly ONE module-local def are followed (ambiguous
# method names across classes disarm the check), and the chase is
# depth-capped and cycle-safe.

_CALL_CHASE_DEPTH = 4


def _local_defs(tree: ast.AST) -> Dict[str, ast.AST]:
    """Terminal name -> def node for unambiguously-named module-local
    functions (top-level defs and methods alike)."""
    seen: Dict[str, Optional[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            seen[node.name] = None if node.name in seen else node
    return {k: v for k, v in seen.items() if v is not None}


def _is_generator(fn: ast.AST) -> bool:
    """True when the def is a generator (contains yield outside nested
    defs): calling it builds an iterator without running the body, so the
    call-site does not execute its statements."""
    return any(isinstance(n, (ast.Yield, ast.YieldFrom))
               for n in _walk_skip_defs(fn))


def _resolve_local_call(call: ast.Call, defs: Dict[str, ast.AST]
                        ) -> Optional[ast.AST]:
    """The module-local def a call targets: ``helper(...)`` or
    ``self.helper(...)``/``cls.helper(...)``; None for anything else
    (external callees, deeper attribute chains, ambiguous names)."""
    name = dotted_name(call.func)
    if not name:
        return None
    parts = name.split(".")
    if len(parts) == 1:
        return defs.get(parts[0])
    if len(parts) == 2 and parts[0] in ("self", "cls"):
        return defs.get(parts[1])
    return None


# -- recompile-hazard -------------------------------------------------------

_NONHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                ast.SetComp)


@register
class RecompileHazard(Rule):
    id = "recompile-hazard"
    description = (
        "Python if/while/range() on a traced jit parameter retraces (or "
        "trace-errors) per value; non-hashable literals for static args "
        "TypeError at dispatch. Mark the arg static or use lax control flow."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for fn, spec in ctx.jit_index.functions.items():
            if spec.unknown:
                continue
            params = _param_names(fn)
            static = set(spec.static_argnames)
            static.update(params[i] for i in spec.static_argnums
                          if 0 <= i < len(params))
            traced = [p for p in params if p not in static]
            if not traced:
                continue
            yield from self._check_body(ctx, fn, set(traced))
        yield from self._check_static_call_sites(ctx)

    def _check_body(self, ctx, fn, traced: Set[str]) -> Iterable[Finding]:
        # Names rebound inside the function are no longer the traced
        # parameter; drop them rather than second-guess data flow.
        traced = traced - _assigned_names(fn)
        for node in _walk_skip_defs(fn):
            if isinstance(node, (ast.If, ast.While)):
                hits = sorted({n.id for n in ast.walk(node.test)
                               if isinstance(n, ast.Name) and n.id in traced})
                if hits:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield self.finding(ctx, node, (
                        f"jitted `{fn.name}` branches with Python `{kind}` on "
                        f"traced parameter(s) {', '.join(hits)} — each new "
                        "value retraces/recompiles (or raises a tracer bool "
                        "error); mark static via static_argnums/"
                        "static_argnames or use jax.lax.cond/jnp.where"))
            elif isinstance(node, ast.For) and isinstance(node.iter, ast.Call) \
                    and dotted_name(node.iter.func) in ("range", "enumerate"):
                hits = sorted({n.id for a in node.iter.args
                               for n in ast.walk(a)
                               if isinstance(n, ast.Name) and n.id in traced})
                if hits:
                    yield self.finding(ctx, node, (
                        f"jitted `{fn.name}` drives `for ... in "
                        f"{dotted_name(node.iter.func)}(...)` with traced "
                        f"parameter(s) {', '.join(hits)} — the loop length "
                        "becomes a fresh trace per value; mark it static or "
                        "use jax.lax.fori_loop/scan"))

    def _check_static_call_sites(self, ctx) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            spec = ctx.jit_index.callables.get(name or "")
            if spec is None or spec.unknown or not spec.static_argnums:
                continue
            for i in spec.static_argnums:
                if 0 <= i < len(node.args) \
                        and isinstance(node.args[i], _NONHASHABLE):
                    yield self.finding(ctx, node.args[i], (
                        f"call to jitted `{name}` passes a non-hashable "
                        f"{type(node.args[i]).__name__.lower()} literal at "
                        f"static position {i} — static args are dict keys of "
                        "the compile cache; pass a tuple or a hashable "
                        "config object"))


# -- rng-reuse --------------------------------------------------------------

# jax.random.* functions that DERIVE keys (their key argument may be used
# again afterwards); everything else in jax.random consumes its key.
_KEY_DERIVERS = {"split", "fold_in", "PRNGKey", "key", "wrap_key_data",
                 "clone", "key_data", "key_impl"}
_KEY_PRODUCERS = {"split", "fold_in", "PRNGKey", "key", "wrap_key_data",
                  "clone"}


def _is_random_chain(name: Optional[str]) -> bool:
    if not name or "." not in name:
        return False
    base = name.rsplit(".", 1)[0]
    return "random" in base.split(".")[-1]


@register
class RngReuse(Rule):
    id = "rng-reuse"
    description = (
        "The same PRNG key consumed by two sampling calls (or by one call "
        "per loop iteration) without an intervening split/fold_in draws "
        "correlated randomness."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        scopes = [ctx.tree] + [n for n in ast.walk(ctx.tree)
                               if isinstance(n, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef))]
        for scope in scopes:
            yield from self._check_scope(ctx, scope)

    # -- one scope ---------------------------------------------------------
    def _check_scope(self, ctx, scope) -> Iterable[Finding]:
        fname = getattr(scope, "name", "<module>")
        body = scope.body
        # tracked key name -> list of (use_repr, branch_path, line)
        state: Dict[str, List[Tuple[str, Tuple, int]]] = {}
        findings: List[Finding] = []
        loop_flagged: Set[Tuple[int, str]] = set()

        # Seed tracking for parameters that this scope evidently treats as
        # PRNG keys: any param fed (bare or subscripted) as the key argument
        # of a jax.random sampling call. A key received from the caller and
        # consumed twice is the classic reuse — producer-bound names alone
        # would miss it.
        if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            params = set(_param_names(scope))
            for n in _walk_skip_defs(scope):
                if not isinstance(n, ast.Call):
                    continue
                callee = dotted_name(n.func)
                terminal = (callee or "").rsplit(".", 1)[-1]
                if not _is_random_chain(callee) or terminal in _KEY_DERIVERS \
                        or not n.args:
                    continue
                a = n.args[0]
                base = a.id if isinstance(a, ast.Name) else (
                    a.value.id if isinstance(a, ast.Subscript)
                    and isinstance(a.value, ast.Name) else None)
                if base in params:
                    state[base] = []

        def paths_compatible(p1: Tuple, p2: Tuple) -> bool:
            shorter, longer = (p1, p2) if len(p1) <= len(p2) else (p2, p1)
            return longer[:len(shorter)] == shorter

        def reprs_overlap(r1: str, r2: str) -> bool:
            if r1 == "*" or r2 == "*":
                return True
            return r1 == r2

        def consume(name: str, use_repr: str, node: ast.AST,
                    path: Tuple, loops: List[Tuple[ast.AST, Set[str], Set[str]]]):
            prior = state.get(name)
            if prior is None:
                return
            for (r1, p1, l1) in prior:
                if reprs_overlap(r1, use_repr) and paths_compatible(p1, path):
                    findings.append(self.finding(ctx, node, (
                        f"PRNG key `{name}` is consumed more than once in "
                        f"`{fname}` without an intervening jax.random.split/"
                        "fold_in — both draws see identical randomness")))
                    break
            prior.append((use_repr, path, node.lineno))
            for (loop, assigned, pre_tracked) in loops:
                if name in pre_tracked and name not in assigned:
                    key_ = (id(loop), name)
                    if key_ not in loop_flagged:
                        loop_flagged.add(key_)
                        findings.append(self.finding(ctx, node, (
                            f"PRNG key `{name}` is consumed inside a loop in "
                            f"`{fname}` but never re-split per iteration — "
                            "every iteration draws identical randomness")))

        def key_use_of(arg: ast.AST) -> Optional[Tuple[str, str]]:
            """(tracked name, use repr) when arg reads a tracked key."""
            if isinstance(arg, ast.Name) and arg.id in state:
                return arg.id, "*"
            if isinstance(arg, ast.Subscript) \
                    and isinstance(arg.value, ast.Name) \
                    and arg.value.id in state:
                try:
                    return arg.value.id, ast.unparse(arg.slice)
                except Exception:  # noqa: BLE001 - repr is best-effort
                    return arg.value.id, "*"
            return None

        def scan_calls(expr: ast.AST, path: Tuple, loops, shadowed: Set[str]):
            if isinstance(expr, ast.Lambda):
                scan_calls(expr.body, path, loops,
                           shadowed | set(_param_names(expr)))
                return
            if isinstance(expr, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return
            if isinstance(expr, ast.Call):
                callee = dotted_name(expr.func)
                terminal = (callee or "").rsplit(".", 1)[-1]
                is_rand = _is_random_chain(callee)
                if not (is_rand and terminal in _KEY_DERIVERS):
                    args = list(expr.args) + [kw.value for kw in expr.keywords]
                    if is_rand:
                        args = expr.args[:1]  # the key position
                    for a in args:
                        got = key_use_of(a)
                        if got and got[0] not in shadowed:
                            consume(got[0], got[1], a, path, loops)
            for child in ast.iter_child_nodes(expr):
                scan_calls(child, path, loops, shadowed)

        def is_producer(value: ast.AST) -> bool:
            if isinstance(value, ast.Call):
                callee = dotted_name(value.func)
                return _is_random_chain(callee) and \
                    (callee or "").rsplit(".", 1)[-1] in _KEY_PRODUCERS
            if isinstance(value, ast.Subscript):
                return is_producer(value.value)
            return False

        def bind_targets(targets, producer: bool):
            for t in targets:
                if isinstance(t, (ast.Tuple, ast.List)):
                    bind_targets(t.elts, producer)
                elif isinstance(t, ast.Name):
                    if producer:
                        state[t.id] = []
                    else:
                        state.pop(t.id, None)

        def run_stmts(stmts, path: Tuple, loops):
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    continue
                if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                    value = stmt.value
                    if value is not None:
                        scan_calls(value, path, loops, set())
                    targets = stmt.targets if isinstance(stmt, ast.Assign) \
                        else [stmt.target]
                    bind_targets(targets, value is not None
                                 and is_producer(value)
                                 and not isinstance(stmt, ast.AugAssign))
                elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                    scan_calls(stmt.iter, path, loops, set())
                    assigned = _assigned_names(stmt)
                    entry = [(stmt, assigned, set(state))]
                    bind_targets([stmt.target], False)
                    run_stmts(stmt.body, path + ((id(stmt), "loop"),),
                              loops + entry)
                    run_stmts(stmt.orelse, path, loops)
                elif isinstance(stmt, ast.While):
                    entry = loops + [(stmt, _assigned_names(stmt), set(state))]
                    scan_calls(stmt.test, path + ((id(stmt), "loop"),),
                               entry, set())
                    run_stmts(stmt.body, path + ((id(stmt), "loop"),), entry)
                    run_stmts(stmt.orelse, path, loops)
                elif isinstance(stmt, ast.If):
                    scan_calls(stmt.test, path, loops, set())
                    run_stmts(stmt.body, path + ((id(stmt), "if"),), loops)
                    run_stmts(stmt.orelse, path + ((id(stmt), "else"),), loops)
                elif isinstance(stmt, ast.Try):
                    run_stmts(stmt.body, path + ((id(stmt), "try"),), loops)
                    for h in stmt.handlers:
                        run_stmts(h.body, path + ((id(stmt), "except"),), loops)
                    run_stmts(stmt.orelse, path + ((id(stmt), "try"),), loops)
                    run_stmts(stmt.finalbody, path, loops)
                elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                    for item in stmt.items:
                        scan_calls(item.context_expr, path, loops, set())
                    run_stmts(stmt.body, path, loops)
                else:
                    scan_calls(stmt, path, loops, set())

        run_stmts(body, (), [])
        return findings


# -- host-sync-in-hot-loop --------------------------------------------------

_SYNC_DOTTED = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                "jax.device_get", "jax.block_until_ready", "device_get",
                "block_until_ready"}
_SYNC_METHODS = {"item", "block_until_ready"}
_HOST_CHEAP_CALLEES = {"len", "min", "max", "str", "int", "repr", "round",
                       "time.time", "time.perf_counter", "time.monotonic"}


@register
class HostSyncInHotLoop(Rule):
    id = "host-sync-in-hot-loop"
    description = (
        "float()/.item()/np.asarray/jax.device_get/block_until_ready running "
        "unconditionally inside a loop that dispatches a jitted step blocks "
        "the host on the device every iteration, so the next step cannot "
        "be dispatched ahead. Gate it behind an interval or accumulate on "
        "device. Syncs nested under an `if` inside the loop are allowed — "
        "that is the interval-gated logging shape."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        parents = _build_parents(ctx.tree)
        defs = _local_defs(ctx.tree)
        reported: Set[int] = set()
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            dispatch = next(
                (c for c in _walk_skip_defs(loop) if isinstance(c, ast.Call)
                 and ctx.jit_index.is_jit_dispatch(c)), None)
            if dispatch is None:
                continue
            fname = _enclosing_function(loop, parents)
            callee = dotted_name(dispatch.func)
            for node, marker in self._sync_calls(loop):
                if id(node) in reported or self._gated(node, loop, parents):
                    continue
                reported.add(id(node))
                yield self.finding(ctx, node, (
                    f"`{marker}` runs unconditionally in a loop in `{fname}` "
                    f"that dispatches jitted `{callee}` — the host blocks on "
                    "the device every iteration; gate it behind an interval, "
                    "hoist it past the loop, or accumulate on device"))
            # Interprocedural: an ungated call to a module-local helper runs
            # the helper body once per iteration, so the helper's own
            # unconditional syncs are loop syncs exactly the same. The
            # `for`-loop iterable is evaluated once, not per iteration, and
            # calling a generator function does not run its body at all —
            # both are excluded.
            iter_ids = {id(n) for n in ast.walk(loop.iter)} \
                if isinstance(loop, (ast.For, ast.AsyncFor)) else set()
            for call in _walk_skip_defs(loop):
                if not isinstance(call, ast.Call) or id(call) in iter_ids \
                        or self._gated(call, loop, parents):
                    continue
                target = _resolve_local_call(call, defs)
                if target is None or target in ctx.jit_index.functions \
                        or _is_generator(target):
                    continue
                yield from self._check_helper(
                    ctx, target, defs, parents, reported, fname, callee,
                    chain=(target.name,), visited={id(target)},
                    depth=_CALL_CHASE_DEPTH)

    def _check_helper(self, ctx, helper, defs, parents, reported: Set[int],
                      loop_fn: str, dispatch_callee, chain, visited,
                      depth: int) -> Iterable[Finding]:
        via = " -> ".join(chain)
        for node, marker in self._sync_calls(helper):
            if id(node) in reported or self._gated(node, helper, parents):
                continue
            reported.add(id(node))
            yield self.finding(ctx, node, (
                f"`{marker}` in `{helper.name}` (reached via {via} from a "
                f"loop in `{loop_fn}` that dispatches jitted "
                f"`{dispatch_callee}`) runs unconditionally every iteration "
                "— the host blocks on the device; gate the call or the "
                "sync behind an interval, or accumulate on device"))
        if depth <= 1:
            return
        for call in _walk_skip_defs(helper):
            if not isinstance(call, ast.Call) \
                    or self._gated(call, helper, parents):
                continue
            target = _resolve_local_call(call, defs)
            if target is None or id(target) in visited \
                    or target in ctx.jit_index.functions:
                continue
            yield from self._check_helper(
                ctx, target, defs, parents, reported, loop_fn,
                dispatch_callee, chain=chain + (target.name,),
                visited=visited | {id(target)}, depth=depth - 1)

    def _sync_calls(self, loop) -> Iterable[Tuple[ast.AST, str]]:
        for n in _walk_skip_defs(loop):
            if not isinstance(n, ast.Call):
                continue
            name = dotted_name(n.func)
            if name == "float" and len(n.args) == 1 \
                    and not self._host_cheap(n.args[0]):
                yield n, "float(...)"
            elif name in _SYNC_DOTTED:
                yield n, f"{name}(...)"
            elif isinstance(n.func, ast.Attribute) \
                    and n.func.attr in _SYNC_METHODS and not n.args \
                    and dotted_name(n.func) is None:
                yield n, f".{n.func.attr}()"
            elif isinstance(n.func, ast.Attribute) \
                    and n.func.attr in _SYNC_METHODS \
                    and dotted_name(n.func) not in _SYNC_DOTTED \
                    and dotted_name(n.func) is not None \
                    and "." in dotted_name(n.func):
                base = dotted_name(n.func).rsplit(".", 1)[0]
                if base not in ("np", "numpy", "math", "time"):
                    yield n, f"{base}.{n.func.attr}()"

    @staticmethod
    def _host_cheap(arg: ast.AST) -> bool:
        if isinstance(arg, ast.Constant):
            return True
        if isinstance(arg, ast.Call):
            return dotted_name(arg.func) in _HOST_CHEAP_CALLEES
        return False

    @staticmethod
    def _gated(node: ast.AST, loop: ast.AST,
               parents: Dict[ast.AST, ast.AST]) -> bool:
        """True when an `if`/`except` between the loop and the sync makes
        the sync conditional per iteration (the allowed, interval-gated
        shape). The tests of If/While are NOT gated — they run every
        iteration."""
        child, cur = node, parents.get(node)
        while cur is not None and cur is not loop:
            if isinstance(cur, ast.If) and child is not cur.test:
                return True
            if isinstance(cur, ast.IfExp) and child is not cur.test:
                return True
            if isinstance(cur, ast.ExceptHandler):
                return True
            if isinstance(cur, ast.BoolOp) and cur.values \
                    and child is not cur.values[0]:
                return True  # short-circuited operand
            child, cur = cur, parents.get(cur)
        return False


# -- use-after-donate -------------------------------------------------------

@register
class UseAfterDonate(Rule):
    id = "use-after-donate"
    description = (
        "An argument passed at a donate_argnums position is aliased into "
        "the output: its buffer is invalid after the call. Reading it again "
        "returns garbage (or errors). Rebind the name from the result."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        parents = _build_parents(ctx.tree)
        for call in ast.walk(ctx.tree):
            if not isinstance(call, ast.Call):
                continue
            spec = ctx.jit_index.callables.get(dotted_name(call.func) or "")
            if spec is None or spec.unknown or not spec.donate_argnums:
                continue
            donated = []
            for i in spec.donate_argnums:
                if 0 <= i < len(call.args):
                    name = dotted_name(call.args[i])
                    if name:
                        donated.append(name)
            if not donated:
                continue
            yield from self._check_call(ctx, call, donated, parents)

    def _check_call(self, ctx, call, donated: List[str], parents
                    ) -> Iterable[Finding]:
        stmt, body = self._enclosing_stmt(call, parents)
        if stmt is None:
            return
        callee = dotted_name(call.func)
        rebound = _assigned_names(stmt)
        live = [d for d in donated if d not in rebound]
        # straight-line: any load of the donated name below the call,
        # before a rebind, in the same statement list
        idx = body.index(stmt)
        for name in list(live):
            for later in body[idx + 1:]:
                use = self._first_load(later, name)
                if use is not None:
                    yield self.finding(ctx, use, (
                        f"`{name}` was donated to jitted `{callee}` "
                        "(donate_argnums) and is read again afterwards — its "
                        "buffer is aliased into the result and no longer "
                        "valid; rebind the name from the call's output"))
                    break
                if name in _assigned_names(later):
                    break
        # loop: the same name donated every iteration without a rebind in
        # the loop body is garbage from iteration 2 on
        cur = parents.get(stmt)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                loop_bound = _assigned_names(cur)
                for name in live:
                    if name not in loop_bound:
                        yield self.finding(ctx, call, (
                            f"`{name}` is donated to jitted `{callee}` "
                            "inside a loop but never rebound in the loop "
                            "body — from the second iteration the call "
                            "consumes an already-donated buffer"))
                break
            cur = parents.get(cur)

    @staticmethod
    def _enclosing_stmt(node, parents):
        cur = node
        while cur in parents:
            parent = parents[cur]
            for field_name in ("body", "orelse", "finalbody"):
                body = getattr(parent, field_name, None)
                if isinstance(body, list) and cur in body:
                    return cur, body
            cur = parent
        return None, None

    @staticmethod
    def _first_load(stmt, name: str):
        for n in _walk_skip_defs(stmt):
            if isinstance(n, (ast.Name, ast.Attribute)) \
                    and isinstance(getattr(n, "ctx", None), ast.Load) \
                    and dotted_name(n) == name:
                return n
        return None


# -- tracer-leak ------------------------------------------------------------

@register
class TracerLeak(Rule):
    id = "tracer-leak"
    description = (
        "Assigning a traced value to self.*/a global inside a jitted "
        "function leaks the tracer out of the trace: jax raises "
        "UnexpectedTracerError, or worse, the attribute silently holds a "
        "stale abstract value after compilation. Return the value instead."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for fn, _spec in ctx.jit_index.functions.items():
            globalish: Set[str] = set()
            for node in ast.walk(fn):
                if isinstance(node, (ast.Global, ast.Nonlocal)):
                    globalish.update(node.names)
            for node in ast.walk(fn):
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    yield from self._check_target(ctx, fn, t, globalish)

    def _check_target(self, ctx, fn, target, globalish: Set[str]
                      ) -> Iterable[Finding]:
        base = target
        while isinstance(base, (ast.Subscript, ast.Attribute)):
            base = base.value
        if isinstance(target, (ast.Attribute, ast.Subscript)) \
                and isinstance(base, ast.Name) and base.id in ("self", "cls"):
            yield self.finding(ctx, target, (
                f"jitted `{fn.name}` assigns to "
                f"`{dotted_name(target) or base.id + '[...]'}` — a traced "
                "value escapes the trace onto the instance; return it from "
                "the function instead"))
        elif isinstance(target, ast.Name) and target.id in globalish:
            yield self.finding(ctx, target, (
                f"jitted `{fn.name}` assigns traced value to "
                f"global/nonlocal `{target.id}` — the tracer escapes the "
                "trace; return it from the function instead"))


# -- jit-in-loop ------------------------------------------------------------

@register
class JitInLoop(Rule):
    id = "jit-in-loop"
    description = (
        "jax.jit called inside a loop builds a fresh wrapper (and a fresh "
        "compile-cache entry keyed on it) every iteration. Hoist the jit "
        "out of the loop, or use a cached factory."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        parents = _build_parents(ctx.tree)
        reported: Set[int] = set()
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            fname = _enclosing_function(loop, parents)
            for node in _walk_skip_defs(loop):
                if isinstance(node, ast.Call) and id(node) not in reported \
                        and jit_spec_of_call(node) is not None:
                    reported.add(id(node))
                    yield self.finding(ctx, node, (
                        f"jax.jit called inside a loop in `{fname}` — every "
                        "iteration creates a new wrapper and misses the "
                        "compile cache; hoist the jit (or a cached factory) "
                        "out of the loop"))


# -- time-in-jit ------------------------------------------------------------

# Wall-clock reads and sleep: inside a trace they run ONCE, at trace time,
# so the "measured" interval is a compile-time constant baked into the
# program (telemetry built on it silently reports the compile, not the
# step — the exact bug obs/flops.py's goodput ledger exists to avoid).
_TRACE_TIME_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
                     "time.process_time", "time.sleep"}
# Blocking host I/O: same trace-once semantics (plus a file handle or
# stdout write the compiled program will never repeat). jax.debug.print /
# jax.debug.callback are the supported in-trace alternatives and do not
# match these bare names.
_TRACE_IO_CALLS = {"open", "print"}


@register
class TimeInJit(Rule):
    id = "time-in-jit"
    description = (
        "time.time()/perf_counter()/sleep(), print() or open() inside a "
        "jitted function runs once at TRACE time, not per call: timings "
        "become compile-time constants and I/O never re-executes. Measure "
        "around the dispatch (after block_until_ready) or use "
        "jax.debug.print/jax.debug.callback for in-trace output."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        defs = _local_defs(ctx.tree)
        reported: Set[int] = set()
        for fn, _spec in ctx.jit_index.functions.items():
            yield from self._check_body(ctx, fn, fn.name, reported, via=None)
            # Interprocedural: a module-local helper called from a jitted
            # body executes at trace time too — its clock reads and I/O
            # freeze into the trace exactly like inline ones.
            yield from self._chase_calls(
                ctx, fn, fn.name, defs, reported,
                chain=(), visited={id(fn)}, depth=_CALL_CHASE_DEPTH)

    def _chase_calls(self, ctx, scope, jit_name: str, defs, reported,
                     chain, visited, depth: int) -> Iterable[Finding]:
        if depth <= 0:
            return
        for call in _walk_skip_defs(scope):
            if not isinstance(call, ast.Call):
                continue
            target = _resolve_local_call(call, defs)
            if target is None or id(target) in visited \
                    or target in ctx.jit_index.functions \
                    or _is_generator(target):
                continue
            sub_chain = chain + (target.name,)
            yield from self._check_body(ctx, target, jit_name, reported,
                                        via=" -> ".join(sub_chain))
            yield from self._chase_calls(
                ctx, target, jit_name, defs, reported, chain=sub_chain,
                visited=visited | {id(target)}, depth=depth - 1)

    def _check_body(self, ctx, scope, jit_name: str, reported: Set[int],
                    via: Optional[str]) -> Iterable[Finding]:
        where = (f"inside jitted `{jit_name}`" if via is None
                 else f"in `{scope.name}` (reached via {via} from jitted "
                      f"`{jit_name}`)")
        for node in _walk_skip_defs(scope):
            if not isinstance(node, ast.Call) or id(node) in reported:
                continue
            name = dotted_name(node.func)
            if name in _TRACE_TIME_CALLS:
                reported.add(id(node))
                yield self.finding(ctx, node, (
                    f"`{name}(...)` {where} runs once "
                    "at trace time — the value is a compile-time "
                    "constant, not a per-step measurement; time around "
                    "the dispatch (after block_until_ready) instead"))
            elif name in _TRACE_IO_CALLS:
                reported.add(id(node))
                yield self.finding(ctx, node, (
                    f"`{name}(...)` {where} executes "
                    "only at trace time — the compiled program never "
                    "repeats the I/O; use jax.debug.print/"
                    "jax.debug.callback for per-call output"))


# -- legacy-shard-map-import ------------------------------------------------

_SHARD_MAP_MOD = "jax.experimental.shard_map"


@register
class LegacyShardMapImport(Rule):
    id = "legacy-shard-map-import"
    description = (
        "jax.experimental.shard_map import: that module path is "
        "deprecated upstream and has other defaults than jax.shard_map, "
        "which the package calls — use jax.shard_map."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == _SHARD_MAP_MOD \
                            or alias.name.startswith(_SHARD_MAP_MOD + "."):
                        yield self._flag(ctx, node, f"import {alias.name}")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if node.level == 0 and (
                        mod == _SHARD_MAP_MOD
                        or mod.startswith(_SHARD_MAP_MOD + ".")):
                    yield self._flag(ctx, node, f"from {mod} import ...")
                elif node.level == 0 and mod == "jax.experimental":
                    for alias in node.names:
                        if alias.name == "shard_map":
                            yield self._flag(
                                ctx, node,
                                "from jax.experimental import shard_map")

    def _flag(self, ctx: ModuleContext, node: ast.AST, form: str) -> Finding:
        return self.finding(ctx, node, (
            f"`{form}` — jax.experimental.shard_map is the deprecated "
            "module path; call jax.shard_map"))


# -- monotonic-clock --------------------------------------------------------

_WALL_CLOCK_CALL = "time.time"


@register
class MonotonicClock(Rule):
    id = "monotonic-clock"
    description = (
        "time.time() is the wall clock: NTP slews and steps it, so a "
        "duration computed as the difference of two readings can jump "
        "backwards or gain seconds mid-measurement (the exact failure the "
        "tracing spans in obs/trace.py exist to keep out of the ledger). "
        "Use time.monotonic() or time.perf_counter() for intervals; keep "
        "time.time() for values that must mean calendar time."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        scopes = [ctx.tree] + [n for n in ast.walk(ctx.tree)
                               if isinstance(n, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef))]
        for scope in scopes:
            yield from self._check_scope(ctx, scope)

    def _check_scope(self, ctx, scope) -> Iterable[Finding]:
        fname = getattr(scope, "name", "<module>")
        # Names bound from a bare time.time() call in this scope. A name
        # ALSO bound from anything else anywhere in the scope is dropped
        # (flow-insensitive, so we cannot order the bindings) — errs
        # toward silence.
        wall: Set[str] = set()
        other: Set[str] = set()
        for n in _walk_skip_defs(scope):
            targets: list = []
            if isinstance(n, ast.Assign):
                targets = n.targets
            elif isinstance(n, (ast.AnnAssign, ast.NamedExpr)) \
                    and n.value is not None:
                targets = [n.target]
            elif isinstance(n, ast.AugAssign):
                targets = [n.target]
            if not targets:
                continue
            is_wall = self._is_wall_call(getattr(n, "value", None)) \
                and not isinstance(n, ast.AugAssign)
            for t in targets:
                name = dotted_name(t)
                if name:
                    (wall if is_wall else other).add(name)
        wall -= other
        for n in _walk_skip_defs(scope):
            if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Sub) \
                    and self._is_wall(n.left, wall) \
                    and self._is_wall(n.right, wall):
                yield self.finding(ctx, n, (
                    f"duration computed by subtracting two time.time() "
                    f"readings in `{fname}` — the wall clock steps under "
                    "NTP, so the interval can be negative or off by "
                    "seconds; use time.monotonic() or time.perf_counter() "
                    "for durations"))

    @staticmethod
    def _is_wall_call(value: Optional[ast.AST]) -> bool:
        return isinstance(value, ast.Call) \
            and dotted_name(value.func) == _WALL_CLOCK_CALL

    def _is_wall(self, node: ast.AST, wall: Set[str]) -> bool:
        if self._is_wall_call(node):
            return True
        name = dotted_name(node)
        return name is not None and name in wall
