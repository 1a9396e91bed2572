"""graftlint core: findings, suppressions, baseline, jit index, runner.

The framework is deliberately jax-free: rules reason about JAX *source
text* (``ast``), never traced values, so the linter runs anywhere Python
runs — no backend init, no device. Rules live in ``rules.py``
and register themselves via :func:`register`; the CLI in ``lint.py`` is
the only entry point that formats or exits.

Three mechanisms decide whether a finding blocks the gate:

- **inline suppression** — ``# graftlint: disable=RULE[,RULE2]`` (or
  ``disable=all``) on the finding's line acknowledges it in place;
- **baseline** — ``baseline.json`` grandfathers known findings, matched
  on ``(rule, path, message)`` (not line numbers, so unrelated edits
  above a finding don't un-baseline it); every entry carries a one-line
  ``reason`` — the gate test enforces that;
- anything else is a **new finding** and the exit code is nonzero.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

PACKAGE_NAME = "mlx_cuda_distributed_pretraining_tpu"

_SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\- ]+)")

# Callable names that look like a compiled step dispatch even when the
# jit wrapping happened in another module (make_train_step & co. return
# jitted callables the call site cannot see).  Matches the terminal
# identifier of the callee: step, step_fn, train_step, eval_step, ...
STEP_NAME_RE = re.compile(r"(^|_)step(_fn)?$")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def key(self) -> Tuple[str, str, str]:
        """Baseline identity: line numbers drift under unrelated edits,
        so matching is on (rule, path, message)."""
        return (self.rule, self.path, self.message)

    def to_dict(self) -> Dict[str, Any]:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message}


# -- rule registry ----------------------------------------------------------

_RULES: Dict[str, "Rule"] = {}


class Rule:
    """One lint rule. Subclasses set ``id``/``description`` and implement
    ``check(ctx) -> iterable of Finding``."""

    id: str = ""
    description: str = ""

    def check(self, ctx: "ModuleContext") -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, ctx: "ModuleContext", node: ast.AST, message: str) -> Finding:
        return Finding(self.id, ctx.path, getattr(node, "lineno", 0),
                       getattr(node, "col_offset", 0), message)


def register(cls):
    """Class decorator: instantiate and add to the global registry."""
    inst = cls()
    assert inst.id and inst.id not in _RULES, f"bad rule id {inst.id!r}"
    _RULES[inst.id] = inst
    return cls


def all_rules() -> Dict[str, Rule]:
    # Import here (not at module top) so core stays importable without the
    # rules and the registry fills exactly once.
    from . import rules as _rules  # noqa: F401

    return dict(_RULES)


# -- jit index --------------------------------------------------------------

@dataclass
class JitSpec:
    """What the linter could statically learn about one jit wrapping."""
    static_argnums: Tuple[int, ...] = ()
    static_argnames: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    # True when any of the above was a non-constant expression — rules
    # must not assert anything about args they can't see.
    unknown: bool = False


@dataclass
class JitIndex:
    """Per-module map of what is jitted.

    - ``functions``: FunctionDef node -> JitSpec for defs that are jitted
      (decorator form, or wrapped by a module-visible ``jax.jit(f, ...)``);
    - ``callables``: dotted-name string (``"step_fn"``, ``"self.eval_step"``)
      -> JitSpec for names bound to a jitted callable, including names
      assigned from a local jit *factory* (a function that returns its own
      jit-decorated inner def — the ``_decode_step`` pattern).
    """
    functions: Dict[ast.AST, JitSpec] = field(default_factory=dict)
    callables: Dict[str, JitSpec] = field(default_factory=dict)
    factories: Dict[str, JitSpec] = field(default_factory=dict)

    def is_jit_dispatch(self, call: ast.Call) -> bool:
        """Heuristic: does this call dispatch a compiled step?  True for
        names proved jitted by this index and for callee names whose
        terminal identifier looks like a step (cross-module factories)."""
        name = dotted_name(call.func)
        if name is None:
            return False
        if name in self.callables:
            return True
        return bool(STEP_NAME_RE.search(name.rsplit(".", 1)[-1]))


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` -> "a.b.c"; None for anything not a pure name chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jax_jit(node: ast.AST) -> bool:
    return dotted_name(node) in ("jax.jit", "jit")


def _const_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant) and isinstance(elt.value, int)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _const_str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant) and isinstance(elt.value, str)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _spec_from_kwargs(keywords: Sequence[ast.keyword]) -> JitSpec:
    spec = JitSpec()
    for kw in keywords:
        if kw.arg == "static_argnums":
            got = _const_int_tuple(kw.value)
            if got is None:
                spec.unknown = True
            else:
                spec.static_argnums = got
        elif kw.arg == "static_argnames":
            got = _const_str_tuple(kw.value)
            if got is None:
                spec.unknown = True
            else:
                spec.static_argnames = got
        elif kw.arg == "donate_argnums":
            got = _const_int_tuple(kw.value)
            if got is None:
                spec.unknown = True
            else:
                spec.donate_argnums = got
    return spec


def jit_spec_of_call(call: ast.Call) -> Optional[JitSpec]:
    """JitSpec when ``call`` is ``jax.jit(...)`` /
    ``partial(jax.jit, ...)``; None otherwise."""
    if _is_jax_jit(call.func):
        return _spec_from_kwargs(call.keywords)
    if dotted_name(call.func) in ("partial", "functools.partial") \
            and call.args and _is_jax_jit(call.args[0]):
        return _spec_from_kwargs(call.keywords)
    return None


def _decorator_spec(fn: ast.AST) -> Optional[JitSpec]:
    for dec in getattr(fn, "decorator_list", []):
        if _is_jax_jit(dec):
            return JitSpec()
        if isinstance(dec, ast.Call):
            spec = jit_spec_of_call(dec)
            if spec is not None:
                return spec
    return None


def build_jit_index(tree: ast.Module) -> JitIndex:
    index = JitIndex()
    defs_by_name: Dict[str, ast.AST] = {}

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, node)
            spec = _decorator_spec(node)
            if spec is not None:
                index.functions[node] = spec
                index.callables.setdefault(node.name, spec)

    # name = jax.jit(fn, ...) / partial-wrapped equivalents
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
            continue
        spec = jit_spec_of_call(node.value)
        if spec is None:
            continue
        wrapped = node.value.args[0] if node.value.args else None
        if _is_jax_jit(node.value.func) and isinstance(wrapped, ast.Name) \
                and wrapped.id in defs_by_name:
            index.functions.setdefault(defs_by_name[wrapped.id], spec)
        for tgt in node.targets:
            name = dotted_name(tgt)
            if name:
                index.callables[name] = spec

    # jit factories: a def that returns its own jit-decorated inner def
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        inner = {n.name: index.functions[n] for n in ast.walk(node)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                 and n is not node and n in index.functions}
        if not inner:
            continue
        for ret in ast.walk(node):
            if isinstance(ret, ast.Return) and isinstance(ret.value, ast.Name) \
                    and ret.value.id in inner:
                index.factories[node.name] = inner[ret.value.id]
                break

    # name = factory(...): the bound name dispatches a jitted callable
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
            continue
        callee = dotted_name(node.value.func)
        if callee in index.factories:
            for tgt in node.targets:
                name = dotted_name(tgt)
                if name:
                    index.callables.setdefault(name, index.factories[callee])
    return index


# -- module context ---------------------------------------------------------

def decorated_header_spans(tree: ast.Module) -> Dict[int, Tuple[int, int]]:
    """line -> (start, end) for every line inside the *header* of a
    decorated def/class: from the first decorator line through the last
    signature line (the line before the body starts). A suppression
    comment anywhere in that span covers findings attributed to any line
    of it — decorators and the ``def`` line are one statement, so a
    ``# graftlint: disable=...`` on the ``def`` line must also cover a
    finding the rule pinned to the decorator above it."""
    spans: Dict[int, Tuple[int, int]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)) \
                and node.decorator_list and node.body:
            start = min(d.lineno for d in node.decorator_list)
            end = node.body[0].lineno - 1
            for ln in range(start, end + 1):
                spans.setdefault(ln, (start, end))
    return spans


def suppressed_rules_at(lines: Sequence[str],
                        header_spans: Dict[int, Tuple[int, int]],
                        line: int,
                        suppress_re: Optional[re.Pattern] = None
                        ) -> Optional[set]:
    """Rule ids suppressed for a finding at ``line`` (None when none):
    the line's own comment, plus — when the line sits in a decorated
    statement's header — comments on every other line of that header.
    ``suppress_re`` lets a sibling tool (graftsync) carry its own
    comment tag; default is the graftlint one."""
    pat = suppress_re or _SUPPRESS_RE

    def line_tags(ln: int) -> Optional[set]:
        if 1 <= ln <= len(lines):
            m = pat.search(lines[ln - 1])
            if m:
                return {r.strip() for r in m.group(1).split(",") if r.strip()}
        return None

    tags = line_tags(line)
    span = header_spans.get(line)
    if span is not None:
        for ln in range(span[0], span[1] + 1):
            if ln == line:
                continue
            extra = line_tags(ln)
            if extra:
                tags = (tags or set()) | extra
    return tags


@dataclass
class ModuleContext:
    path: str          # normalized (package-relative when possible)
    abspath: str
    tree: ast.Module
    lines: List[str]
    jit_index: JitIndex
    header_spans: Dict[int, Tuple[int, int]] = field(default_factory=dict)
    # Tools sharing this runner but carrying their own comment tag
    # (graftsync: ``# graftsync: disable=RULE``) set this; None means
    # the graftlint tag.
    suppress_re: Optional[re.Pattern] = None

    def suppressed_rules(self, line: int) -> Optional[set]:
        return suppressed_rules_at(self.lines, self.header_spans, line,
                                   suppress_re=self.suppress_re)


def normalize_path(path: str) -> str:
    """Stable finding/baseline path: relative to the package parent when
    the file lives under the package, else relative to CWD, else absolute
    — always posix separators."""
    ap = os.path.abspath(path)
    parts = ap.split(os.sep)
    if PACKAGE_NAME in parts:
        idx = len(parts) - 1 - parts[::-1].index(PACKAGE_NAME)
        return "/".join(parts[idx:])
    rel = os.path.relpath(ap, os.getcwd())
    return rel.replace(os.sep, "/") if not rel.startswith("..") \
        else ap.replace(os.sep, "/")


def _iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if f.endswith(".py"))
        elif p.endswith(".py"):
            out.append(p)
    return out


# -- baseline ---------------------------------------------------------------

def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: Optional[str]) -> List[Dict[str, Any]]:
    path = path or default_baseline_path()
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        doc = json.load(f)
    return list(doc.get("findings", []))


def write_baseline(path: str, findings: Sequence[Finding],
                   old_entries: Sequence[Dict[str, Any]] = (),
                   tool: str = "graftlint") -> None:
    """Regenerate the baseline from the current findings, preserving the
    reason of any entry that still matches. New entries get a placeholder
    reason the gate test rejects — a human must justify each one."""
    reasons = {(e.get("rule"), e.get("path"), e.get("message")): e.get("reason")
               for e in old_entries}
    entries = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        entries.append({
            **f.to_dict(),
            "reason": reasons.get(f.key())
            or "grandfathered by --write-baseline — REPLACE with a one-line justification",
        })
    with open(path, "w") as fh:
        json.dump({"version": 1, "tool": tool, "findings": entries},
                  fh, indent=2)
        fh.write("\n")


def write_baseline_entries(path: str, entries: Sequence[Dict[str, Any]],
                           tool: str = "graftlint") -> None:
    """Write pre-built baseline entries verbatim (used by --prune-stale,
    which must keep surviving entries byte-identical, reasons included)."""
    with open(path, "w") as fh:
        json.dump({"version": 1, "tool": tool,
                   "findings": list(entries)}, fh, indent=2)
        fh.write("\n")


# -- runner -----------------------------------------------------------------

@dataclass
class LintResult:
    findings: List[Finding]            # everything rules reported
    suppressed: List[Finding]          # acknowledged inline
    baselined: List[Finding]           # matched a baseline entry
    new: List[Finding]                 # what the gate fails on
    stale_baseline: List[Dict[str, Any]]  # baseline entries nothing matched


def classify_findings(findings: Sequence[Finding],
                      baseline: Optional[Sequence[Dict[str, Any]]]
                      ) -> Tuple[List[Finding], List[Finding],
                                 List[Dict[str, Any]]]:
    """Multiset-match findings against the baseline: N identical entries
    excuse at most N identical findings. Returns (baselined, new, stale);
    stale entries matched nothing — the finding they excused was fixed.
    Shared by graftlint (source findings) and graftaudit (lowered-program
    findings): both gate the same way."""
    budget: Dict[Tuple[str, str, str], int] = {}
    for e in baseline or ():
        k = (e.get("rule"), e.get("path"), e.get("message"))
        budget[k] = budget.get(k, 0) + 1
    baselined: List[Finding] = []
    new: List[Finding] = []
    for f in findings:
        if budget.get(f.key(), 0) > 0:
            budget[f.key()] -= 1
            baselined.append(f)
        else:
            new.append(f)
    stale: List[Dict[str, Any]] = []
    leftover = dict(budget)
    for e in baseline or ():
        k = (e.get("rule"), e.get("path"), e.get("message"))
        if leftover.get(k, 0) > 0:
            leftover[k] -= 1
            stale.append(dict(e))
    return baselined, new, stale


def result_to_json(tool: str, result: LintResult) -> Dict[str, Any]:
    """The stable machine-readable document both CLIs emit under
    ``--format json``. Top-level keys
    ``new``/``baselined``/``suppressed``/``stale_baseline`` are kept for
    existing consumers; ``findings`` is the flat per-finding schema
    (rule, path, line, col, message, baselined, suppressed)."""
    def flat(f: Finding, *, baselined: bool = False,
             suppressed: bool = False) -> Dict[str, Any]:
        return {**f.to_dict(), "baselined": baselined,
                "suppressed": suppressed}

    return {
        "tool": tool,
        "new": [f.to_dict() for f in result.new],
        "baselined": [f.to_dict() for f in result.baselined],
        "suppressed": [f.to_dict() for f in result.suppressed],
        "stale_baseline": [dict(e) for e in result.stale_baseline],
        "findings": [flat(f) for f in result.new]
        + [flat(f, baselined=True) for f in result.baselined]
        + [flat(f, suppressed=True) for f in result.suppressed],
    }


def lint_file(path: str, rules: Optional[Dict[str, Rule]] = None,
              suppress_re: Optional[re.Pattern] = None
              ) -> Tuple[List[Finding], List[Finding]]:
    """Lint one file. Returns (active findings, inline-suppressed)."""
    rules = rules if rules is not None else all_rules()
    ap = os.path.abspath(path)
    norm = normalize_path(path)
    try:
        with open(ap, encoding="utf-8") as f:
            src = f.read()
        tree = ast.parse(src, filename=ap)
    except (OSError, SyntaxError) as e:
        lineno = getattr(e, "lineno", 0) or 0
        return [Finding("parse-error", norm, lineno, 0,
                        f"{type(e).__name__}: {e}")], []
    ctx = ModuleContext(norm, ap, tree, src.splitlines(),
                        build_jit_index(tree),
                        header_spans=decorated_header_spans(tree),
                        suppress_re=suppress_re)
    active: List[Finding] = []
    suppressed: List[Finding] = []
    for rule in rules.values():
        for f in rule.check(ctx):
            tags = ctx.suppressed_rules(f.line)
            if tags is not None and ("all" in tags or f.rule in tags):
                suppressed.append(f)
            else:
                active.append(f)
    active.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return active, suppressed


def run_lint(paths: Sequence[str],
             baseline: Optional[Sequence[Dict[str, Any]]] = None,
             rules: Optional[Dict[str, Rule]] = None,
             suppress_re: Optional[re.Pattern] = None) -> LintResult:
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for fp in _iter_py_files(paths):
        got, sup = lint_file(fp, rules=rules, suppress_re=suppress_re)
        findings.extend(got)
        suppressed.extend(sup)

    baselined, new, stale = classify_findings(findings, baseline)
    return LintResult(findings=findings, suppressed=suppressed,
                      baselined=baselined, new=new, stale_baseline=stale)
