"""graftaudit rules: audits over LOWERED programs, not source text.

graftlint (rules.py) reads the AST; the rules here read what XLA will
actually run. analysis/audit.py AOT-lowers the real train/serve/decode
steps under abstract inputs (``jax.jit(...).trace(...).lower()`` — no
device execution, CPU-safe) and hands each rule an :class:`AuditProgram`
wrapping the jaxpr, the donation metadata, the compiled HLO text, and
the compiled input shardings. Every deviation becomes a graftlint-style
:class:`~.core.Finding`, gated through the same baseline/suppression
machinery.

Rules:

- ``donation-gap``       — a large un-donated input whose (shape, dtype)
  also appears in the outputs is a buffer the step updates without
  aliasing: HBM is paying for two copies. Donated inputs consume output
  matches first, so read-only args (decode params) never flag.
- ``collective-census``  — counts/bytes of every collective in the
  compiled HLO, diffed against the committed per-config budget
  (analysis/budgets/*.json). GSPMD inserts collectives during XLA
  compilation — they are invisible in the jaxpr — so this parses the
  post-optimization HLO text. A regression fails; a shrink asks for a
  budget refresh (scripts/audit_budget.py).
- ``dtype-upcast``       — ``dot_general``/``conv`` whose operands are
  all fp32 in a program whose config says bf16 compute: a matmul that
  silently runs at 4x the flops cost of the configured precision.
- ``large-constant-capture`` — closed-over arrays baked into the jaxpr
  (``closed_jaxpr.consts``) above a size threshold: they are re-shipped
  with every executable instead of living in one donated buffer.
- ``replicated-param``   — a param leaf whose compiled input sharding is
  fully replicated while parallel/sharding_rules.py::param_pspec names a
  sharded axis for it: the sharding annotation was lost on the way to
  the compiler.
- ``dequant-materialization`` — a quantized weight tensor (int8
  ``weight_q`` / packed-int4 ``weight_q4`` input leaf) whose dequantized
  fp copy the program MATERIALIZES: the int→fp convert's result escapes
  as an output, is reused by several consumers, or feeds anything other
  than a single contraction. The healthy lowering keeps the fp copy a
  transient operand of exactly one dot (unpack+scale fused into the
  matmul epilogue); a resident fp copy (≥ 2x the int bytes) forfeits the
  bandwidth win weight-only quantization exists for. Reads the jaxpr,
  not the HLO: XLA:CPU spells the per-matmul convert as a standalone
  fusion (transient scratch, not a resident copy), so fusion-level HLO
  would false-positive on every CPU-hosted audit.
- ``sync-collectives``   — the config requested a latency-hiding XLA
  flag set (``system.xla.flag_set``) yet the train program's
  overlap-relevant collectives (all-gather / reduce-scatter /
  all-reduce) lowered in their synchronous form: the flag set was
  dropped (set after backend init, or not in ``XLA_FLAGS`` at all) and
  every collective sits exposed on the critical path. Only meaningful
  on backends whose flag set is non-empty — XLA:CPU resolves to ()
  (parallel/xla_flags.py), so CPU-hosted audits never fire it.
"""

from __future__ import annotations

import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .core import Finding, normalize_path

# -- program wrapper ---------------------------------------------------------


@dataclass(frozen=True)
class ArgLeaf:
    """One flattened leaf of one positional argument of a lowered step."""

    index: int      # positional index in the step signature
    name: str       # signature name of the top-level argument
    path: str       # dotted keypath inside the argument ("" for a scalar arg)
    shape: Tuple[int, ...]
    dtype: str
    nbytes: int
    donated: bool


@dataclass
class AuditProgram:
    """Everything the audit rules need about one lowered step.

    ``lowered`` is a ``jax.stages.Lowered``; compilation (needed for the
    HLO census and input shardings) happens lazily and once.
    """

    name: str                       # "train_step", "serve_decode", ...
    config_name: str                # config stem, e.g. "model-config-sample"
    lowered: Any
    closed_jaxpr: Any
    arg_leaves: List[ArgLeaf]
    out_avals: List[Any]
    compute_dtype: str = "float32"
    # Param leaves that sharding_rules EXPECTS sharded: full dotted path
    # within positional arg `param_arg_index` -> expected spec string.
    param_arg_index: Optional[int] = None
    expected_param_specs: Dict[str, str] = field(default_factory=dict)
    # Committed collective budget for this (config, program), or None.
    budget: Optional[Dict[str, Dict[str, int]]] = None
    # What system.xla.flag_set asked for, and the backend the lowering
    # targeted — the sync-collectives rule compares the two against the
    # HLO that actually came out.
    requested_flag_set: Optional[str] = None
    flag_backend: str = "cpu"
    _compiled: Any = None
    _census: Optional[Dict[str, Dict[str, int]]] = None

    @property
    def synthetic_path(self) -> str:
        """Stable pseudo-path for findings with no source location."""
        return f"<{self.config_name}:{self.name}>"

    def compiled(self):
        if self._compiled is None:
            self._compiled = self.lowered.compile()
        return self._compiled

    def census(self) -> Dict[str, Dict[str, int]]:
        if self._census is None:
            self._census = parse_hlo_census(self.compiled().as_text())
        return self._census

    def donation_summary(self) -> Dict[str, int]:
        """Budget-file material: how many bytes the step aliases in place
        and how many it provably could but does not (the gap)."""
        donated = sum(l.nbytes for l in self.arg_leaves if l.donated)
        gap = sum(l.nbytes for _, leaves in _donation_gaps(self)
                  for l in leaves)
        return {"donated_bytes": donated, "gap_bytes": gap}


# -- shared helpers ----------------------------------------------------------


def fmt_bytes(n: int) -> str:
    if n >= 1 << 30:
        return f"{n / (1 << 30):.2f} GiB"
    if n >= 1 << 20:
        return f"{n / (1 << 20):.1f} MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f} KiB"
    return f"{n} B"


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Walk every equation, descending into sub-jaxprs (scan bodies,
    cond branches, remat/pjit calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)
                elif hasattr(sub, "eqns"):
                    yield from iter_eqns(sub)


def eqn_frame(eqn) -> Optional[Tuple[str, int, str]]:
    """(file, line, function) of the user code that traced this equation."""
    from jax._src import source_info_util

    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return None
    return fr.file_name, fr.start_line, fr.function_name


# HLO instruction: `%name = <shape> <opcode>(...)`. The optional -start
# suffix counts async pairs once; -done never matches (no "(" after it).
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute", "collective-broadcast")
_COLL_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[a-z0-9_\[\]{},]+)\s+"
    r"(?P<op>" + "|".join(_COLL_OPS) + r")(?:-start)?\(")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\]")


def _dtype_bytes(dt: str) -> int:
    if dt == "pred":
        return 1
    m = re.match(r"[a-z]+?(\d+)", dt)  # f32 -> 32, bf16 -> 16, f8e4m3fn -> 8
    return max(int(m.group(1)) // 8, 1) if m else 4


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_text):
        n = 1
        for d in m.group("dims").split(","):
            if d:
                n *= int(d)
        total += n * _dtype_bytes(m.group("dt"))
    return total


def parse_hlo_census(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Per-collective-op {count, bytes} from post-optimization HLO text.

    Bytes are the (per-device) output shape of each collective — a
    stable, layout-independent regression metric, not a wire-byte model."""
    census: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        entry = census.setdefault(m.group("op"), {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += _shape_bytes(m.group("shape"))
    return census


# Collectives the latency-hiding flag sets exist to overlap. Async HLO
# spells them `<op>-start`/`<op>-done`; the plain form is synchronous and
# sits exposed on the critical path. `<op>(` with no suffix matches only
# the sync spelling (`-start(`/`-done(` put a suffix between op and paren).
_OVERLAP_OPS = ("all-gather", "all-reduce", "reduce-scatter")
_SYNC_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9_\[\]{},]+)\s+"
    r"(?P<op>" + "|".join(_OVERLAP_OPS) + r")\(")


def sync_collective_census(hlo_text: str) -> Dict[str, int]:
    """Per-op count of SYNCHRONOUS overlap-relevant collectives in
    post-optimization HLO text (async -start/-done pairs do not count)."""
    census: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _SYNC_COLL_RE.search(line)
        if m:
            census[m.group("op")] = census.get(m.group("op"), 0) + 1
    return census


def _aval_key(aval) -> Optional[Tuple[Tuple[int, ...], str]]:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None
    return tuple(shape), str(dtype)


# Group-level floor: a gap must be worth chasing before it pages anyone.
_GAP_MIN_BYTES = 64 * 1024
_GAP_MIN_FRACTION = 0.05  # of the program's total input bytes
_CONST_MIN_BYTES = 128 * 1024


def _donation_gaps(prog: AuditProgram) -> List[Tuple[Tuple[int, str], List[ArgLeaf]]]:
    """Undonated input leaves whose (shape, dtype) the program also
    returns, grouped by top-level argument — the in/out "updated state"
    pairs donation exists for. Donated inputs consume output matches
    first, so a read-only arg that merely shapes like an output (decode
    params vs logits never match; params vs new-params in a train step
    do, and ARE the gap when not donated)."""
    pool: Counter = Counter()
    for aval in prog.out_avals:
        k = _aval_key(aval)
        if k is not None:
            pool[k] += 1
    for leaf in prog.arg_leaves:
        if leaf.donated and pool.get((leaf.shape, leaf.dtype), 0) > 0:
            pool[(leaf.shape, leaf.dtype)] -= 1
    total = sum(l.nbytes for l in prog.arg_leaves) or 1
    floor = max(_GAP_MIN_BYTES, int(_GAP_MIN_FRACTION * total))
    groups: Dict[Tuple[int, str], List[ArgLeaf]] = defaultdict(list)
    for leaf in prog.arg_leaves:
        if leaf.donated:
            continue
        k = (leaf.shape, leaf.dtype)
        if pool.get(k, 0) > 0:
            pool[k] -= 1
            groups[(leaf.index, leaf.name)].append(leaf)
    return sorted((key, leaves) for key, leaves in groups.items()
                  if sum(l.nbytes for l in leaves) >= floor)


# -- the rules ---------------------------------------------------------------


class DonationGap:
    id = "donation-gap"
    description = ("large un-donated input whose shape/dtype the program "
                   "returns updated — HBM holds two copies per step")

    def check(self, prog: AuditProgram) -> Iterable[Finding]:
        for (idx, name), leaves in _donation_gaps(prog):
            waste = sum(l.nbytes for l in leaves)
            yield Finding(
                self.id, prog.synthetic_path, 0, 0,
                f"program `{prog.name}`: argument {idx} (`{name}`) has "
                f"{len(leaves)} un-donated buffer(s) totalling "
                f"{fmt_bytes(waste)} that the step returns updated "
                f"(matching shape/dtype out) — donate it to alias the "
                f"update in place (estimated waste {fmt_bytes(waste)})")


class CollectiveCensus:
    id = "collective-census"
    description = ("collective count/bytes in the compiled HLO exceed the "
                   "committed per-config budget (analysis/budgets/)")

    def check(self, prog: AuditProgram) -> Iterable[Finding]:
        census = prog.census()
        if prog.budget is None:
            if census:
                ops = ", ".join(f"{op} x{c['count']}"
                                for op, c in sorted(census.items()))
                yield Finding(
                    self.id, prog.synthetic_path, 0, 0,
                    f"program `{prog.name}` emits collectives ({ops}) but "
                    f"has no committed budget — run scripts/audit_budget.py "
                    f"to record one")
            return
        for op, got in sorted(census.items()):
            want = prog.budget.get(op, {"count": 0, "bytes": 0})
            if got["count"] > want["count"] or got["bytes"] > want["bytes"]:
                yield Finding(
                    self.id, prog.synthetic_path, 0, 0,
                    f"program `{prog.name}`: {op} regressed — "
                    f"{got['count']} op(s) / {fmt_bytes(got['bytes'])} vs "
                    f"budget {want['count']} op(s) / "
                    f"{fmt_bytes(want['bytes'])}; if intentional, refresh "
                    f"with scripts/audit_budget.py")


class DtypeUpcast:
    id = "dtype-upcast"
    description = ("fp32-operand dot/conv in a bf16-compute program — the "
                   "matmul silently runs at fp32 cost")

    _PRIMS = ("dot_general", "conv_general_dilated")

    def check(self, prog: AuditProgram) -> Iterable[Finding]:
        if prog.compute_dtype != "bfloat16":
            return
        seen = set()
        for eqn in iter_eqns(prog.closed_jaxpr.jaxpr):
            if eqn.primitive.name not in self._PRIMS:
                continue
            dtypes = [str(getattr(v.aval, "dtype", ""))
                      for v in eqn.invars if hasattr(v, "aval")]
            if not dtypes or any(d != "float32" for d in dtypes):
                continue
            frame = eqn_frame(eqn)
            if frame is None:
                path, line, where = prog.synthetic_path, 0, prog.name
            else:
                path, line, where = (normalize_path(frame[0]), frame[1],
                                     f"`{frame[2]}`")
            key = (path, line, eqn.primitive.name)
            if key in seen:
                continue
            seen.add(key)
            shapes = " @ ".join(
                str(tuple(v.aval.shape)) for v in eqn.invars[:2]
                if hasattr(v, "aval"))
            yield Finding(
                self.id, path, line, 0,
                f"fp32 {eqn.primitive.name} ({shapes}) traced from {where} "
                f"in bf16-compute program `{prog.name}` — cast the operands "
                f"to the compute dtype (or suppress if fp32 is deliberate)")


class LargeConstantCapture:
    id = "large-constant-capture"
    description = ("closed-over array baked into the jaxpr above "
                   f"{fmt_bytes(_CONST_MIN_BYTES)} — pass it as an argument")

    def check(self, prog: AuditProgram) -> Iterable[Finding]:
        for const in getattr(prog.closed_jaxpr, "consts", ()):
            shape = getattr(const, "shape", None)
            dtype = getattr(const, "dtype", None)
            if shape is None or dtype is None:
                continue
            n = 1
            for d in shape:
                n *= int(d)
            nbytes = n * getattr(dtype, "itemsize", 4)
            if nbytes < _CONST_MIN_BYTES:
                continue
            yield Finding(
                self.id, prog.synthetic_path, 0, 0,
                f"program `{prog.name}`: closed-over constant {dtype}"
                f"{tuple(shape)} ({fmt_bytes(nbytes)}) is baked into the "
                f"jaxpr — it is re-staged with every executable; pass it "
                f"as an argument instead")


class ReplicatedParam:
    id = "replicated-param"
    description = ("param leaf lowered fully replicated although "
                   "sharding_rules.param_pspec names a sharded axis")

    def check(self, prog: AuditProgram) -> Iterable[Finding]:
        if prog.param_arg_index is None or not prog.expected_param_specs:
            return
        import jax.tree_util as jtu

        args_shardings = prog.compiled().input_shardings[0]
        arg = args_shardings[prog.param_arg_index]
        flat, _ = jtu.tree_flatten_with_path(arg)
        actual = {_keypath_str(kp): sh for kp, sh in flat}
        for path, expected in sorted(prog.expected_param_specs.items()):
            sh = actual.get(path)
            if sh is None:
                continue
            try:
                replicated = bool(sh.is_fully_replicated)
            except AttributeError:
                continue
            if replicated:
                yield Finding(
                    self.id, prog.synthetic_path, 0, 0,
                    f"program `{prog.name}`: param `{path}` lowered fully "
                    f"replicated but sharding rules expect {expected} — "
                    f"the in_shardings wiring dropped it")


_DEQUANT_MIN_BYTES = 64 * 1024
# Layout-only ops an fp weight may pass through on its way into the one
# contraction that consumes it (transpose for `x @ w.T`-style applies).
_DEQUANT_PASS_THROUGH = ("transpose", "reshape", "broadcast_in_dim",
                         "squeeze", "expand_dims")
_CONTRACTION_PRIMS = ("dot_general", "conv_general_dilated")


class DequantMaterialization:
    id = "dequant-materialization"
    description = ("quantized weight dequantized into a resident fp copy "
                   "instead of a transient single-contraction operand")

    def check(self, prog: AuditProgram) -> Iterable[Finding]:
        jaxpr = prog.closed_jaxpr.jaxpr
        if len(jaxpr.invars) != len(prog.arg_leaves):
            return
        taint = {}
        for var, leaf in zip(jaxpr.invars, prog.arg_leaves):
            base = leaf.path.rsplit(".", 1)[-1]
            if base in ("weight_q", "weight_q4") and "int" in leaf.dtype:
                taint[var] = leaf.path
        if not taint:
            return
        self._seen: set = set()
        yield from self._walk(prog, jaxpr, taint)

    # -- taint walk ----------------------------------------------------------

    @staticmethod
    def _is_var(v) -> bool:
        return type(v).__name__ not in ("Literal", "DropVar")

    def _walk(self, prog, jaxpr, taint) -> Iterable[Finding]:
        consumers: Dict[Any, List[Any]] = defaultdict(list)
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if self._is_var(v):
                    consumers[v].append(eqn)
        outset = {v for v in jaxpr.outvars if self._is_var(v)}

        for eqn in jaxpr.eqns:
            hit = [v for v in eqn.invars if self._is_var(v) and v in taint]
            if hit:
                src_path = taint[hit[0]]
                prim = eqn.primitive.name
                out_dtypes = [getattr(v.aval, "dtype", None)
                              for v in eqn.outvars if hasattr(v, "aval")]
                if (prim == "convert_element_type" and out_dtypes
                        and all(d is not None and d.kind == "f"
                                for d in out_dtypes)):
                    yield from self._check_convert(
                        prog, eqn, hit[0], src_path, consumers, outset)
                elif out_dtypes and all(d is not None and d.kind in "iu"
                                        for d in out_dtypes):
                    # still the int plane (int4 unpack shifts/concat,
                    # slicing, layout): keep following it.
                    for v in eqn.outvars:
                        if self._is_var(v):
                            taint[v] = src_path
            # descend into call-like sub-jaxprs (pjit, remat, scan bodies)
            # where the positional invar mapping is 1:1.
            for pv in eqn.params.values():
                for sub in (pv if isinstance(pv, (list, tuple)) else (pv,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if not hasattr(inner, "eqns") or not hasattr(inner, "invars"):
                        continue
                    if len(inner.invars) != len(eqn.invars):
                        continue
                    inner_taint = {
                        iv: taint[ov]
                        for iv, ov in zip(inner.invars, eqn.invars)
                        if self._is_var(ov) and ov in taint}
                    if inner_taint:
                        yield from self._walk(prog, inner, inner_taint)

    def _check_convert(self, prog, eqn, src_var, src_path, consumers,
                       outset) -> Iterable[Finding]:
        out = eqn.outvars[0]
        in_aval, out_aval = src_var.aval, out.aval
        in_bytes = in_aval.size * in_aval.dtype.itemsize
        out_bytes = out_aval.size * out_aval.dtype.itemsize
        if out_bytes < max(2 * in_bytes, _DEQUANT_MIN_BYTES):
            return
        why = self._materialized(out, consumers, outset)
        if why is None:
            return
        frame = eqn_frame(eqn)
        if frame is None:
            path, line, where = prog.synthetic_path, 0, prog.name
        else:
            path, line, where = (normalize_path(frame[0]), frame[1],
                                 f"`{frame[2]}`")
        key = (path, line, src_path)
        if key in self._seen:
            return
        self._seen.add(key)
        yield Finding(
            self.id, path, line, 0,
            f"program `{prog.name}`: quantized weight `{src_path}` "
            f"({fmt_bytes(in_bytes)} int) is dequantized into a resident "
            f"{fmt_bytes(out_bytes)} fp copy at {where} — {why}; keep the "
            f"fp form a transient operand of exactly one matmul so the "
            f"convert fuses into the contraction epilogue")

    def _materialized(self, var, consumers, outset) -> Optional[str]:
        """None if the fp copy is a transient single-contraction operand;
        else the reason it must stay resident."""
        for _ in range(8):  # bounded pass-through chain
            if var in outset:
                return "it escapes as a program output"
            cons = consumers.get(var, [])
            if not cons:
                return None  # dead value: DCE's problem, not HBM's
            if len(cons) > 1:
                return f"it is reused by {len(cons)} consumers"
            prim = cons[0].primitive.name
            if prim in _CONTRACTION_PRIMS:
                return None
            if prim not in _DEQUANT_PASS_THROUGH:
                # A call-like consumer (scan/pjit body) re-enters _walk via
                # the int plane when the convert lives inside; an fp weight
                # handed ACROSS the boundary was converted too early.
                if any(hasattr(getattr(s, "jaxpr", s), "eqns")
                       for pv in cons[0].params.values()
                       for s in (pv if isinstance(pv, (list, tuple)) else (pv,))):
                    return None  # conservative: don't flag call boundaries
                return f"it feeds `{prim}`, not a contraction"
            var = cons[0].outvars[0]
        return "its consumer chain never reaches a contraction"


class SyncCollectives:
    id = "sync-collectives"
    description = ("overlap-relevant collectives lowered synchronous although "
                   "the config requested a latency-hiding XLA flag set")

    def check(self, prog: AuditProgram) -> Iterable[Finding]:
        if prog.name != "train_step" or not prog.requested_flag_set:
            return
        from ..parallel import xla_flags

        try:
            flags = xla_flags.flags_for(prog.requested_flag_set,
                                        prog.flag_backend)
        except ValueError:
            return  # config validation owns unknown set names
        if not flags:
            # The backend has nothing to set (XLA:CPU): sync collectives
            # are the only spelling it has, not a dropped flag set.
            return
        sync = sync_collective_census(prog.compiled().as_text())
        if not sync:
            return
        missing = xla_flags.missing_flags(prog.requested_flag_set,
                                          prog.flag_backend)
        ops = ", ".join(f"{op} x{n}" for op, n in sorted(sync.items()))
        msg = (f"program `{prog.name}`: {sum(sync.values())} synchronous "
               f"overlap-relevant collective(s) ({ops}) although the config "
               f"requested xla flag set `{prog.requested_flag_set}` for "
               f"backend `{prog.flag_backend}`")
        if missing:
            msg += (" — flags missing from XLA_FLAGS: "
                    + " ".join(missing)
                    + " (apply_flag_set must run before backend init; "
                      "see parallel/xla_flags.py)")
        else:
            msg += (" — the flags are in XLA_FLAGS but the compiler still "
                    "emitted sync forms; check scheduler eligibility "
                    "(fusion thresholds, program size)")
        yield Finding(self.id, prog.synthetic_path, 0, 0, msg)


def _keypath_str(kp) -> str:
    parts = []
    for p in kp:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


_AUDIT_RULES = [DonationGap(), CollectiveCensus(), DtypeUpcast(),
                LargeConstantCapture(), ReplicatedParam(),
                DequantMaterialization(), SyncCollectives()]


def all_audit_rules() -> Dict[str, Any]:
    return {r.id: r for r in _AUDIT_RULES}


def audit_program(prog: AuditProgram) -> List[Finding]:
    findings: List[Finding] = []
    for rule in _AUDIT_RULES:
        findings.extend(rule.check(prog))
    return findings
