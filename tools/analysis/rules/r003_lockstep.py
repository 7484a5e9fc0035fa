"""RPCA-R003 — collective lock-step.

Invariant (PR 7, DESIGN.md Sec. 14): every process in a multi-process
mesh must execute the *same sequence* of collectives.  Inside a
``shard_map`` body (or anything transitively called from one), a
``psum``/``pmean``/``all_gather``/``ppermute`` reachable under a Python
``if``/``while`` whose condition depends on *non-replicated* values can
fire on some hosts and not others => deadlock or silent divergence.

Taint model (conservative, tuned for zero FPs on this repo):

* Taint sources: parameters of the shard_map body (they are per-shard
  values) and results of ``axis_index``/``process_index``.
* Propagation: through assignments, arithmetic, subscripts and calls
  whose arguments are tainted.
* Pruning (provably replicated / trace-time static):
  - ``x is None`` / ``x is not None`` tests (structure, not data),
  - ``isinstance(...)``, ``len(...)``, string-literal ``in`` tests,
  - attribute reads of static properties: ``.ndim``, ``.shape``,
    ``.dtype``, ``.size`` (same on every shard),
  - names never tainted (closure constants, config).
* A collective call is flagged when it sits lexically inside the body or
  orelse of a tainted ``if``/``while``.  Both branches are hazard
  regions (the *other* processes take the other branch).

``jax.lax.axis_index`` is a taint *source* but not itself a flagged
collective (it is a local computation, safe under divergence).
"""
from __future__ import annotations

import ast

from tools.analysis.core import Finding, ModuleInfo, Rule, dotted_name

_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "psum_scatter",
}
_TAINT_SOURCE_CALLS = {"axis_index", "process_index"}
_STATIC_ATTRS = {"ndim", "shape", "dtype", "size", "sharding"}
_SHARD_MAP_NAMES = {"shard_map", "pmap", "jax.pmap"}


def _call_basename(node: ast.Call) -> str | None:
    """Last component of the callee name: ``jax.lax.psum`` -> ``psum``."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _shard_map_body_names(mod: ModuleInfo) -> dict[str, int]:
    """Names of functions passed (positionally or by reference) to
    shard_map / pmap -> call line."""
    out: dict[str, int] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        base = _call_basename(node)
        d = dotted_name(node.func)
        if base in _SHARD_MAP_NAMES or d in _SHARD_MAP_NAMES:
            for arg in node.args[:1]:  # body fn is the first positional
                if isinstance(arg, ast.Name):
                    out[arg.id] = node.lineno
    return out


def _is_static_test(test: ast.AST, tainted: set[str]) -> bool:
    """True when a condition is provably identical across processes."""
    # `x is None` / `x is not None`
    if isinstance(test, ast.Compare):
        ops = test.ops
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in ops):
            return True
        # string-literal `in` membership ("v" in packed)
        if all(isinstance(op, (ast.In, ast.NotIn)) for op in ops):
            if isinstance(test.left, ast.Constant):
                return True
        # comparisons on untainted values fall through to taint check
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_static_test(test.operand, tainted)
    if isinstance(test, ast.BoolOp):
        return all(_is_static_test(v, tainted) for v in test.values)
    if isinstance(test, ast.Call):
        base = _call_basename(test)
        if base in ("isinstance", "len", "hasattr", "callable"):
            return True
    # finally: untainted expressions are replicated by construction
    return not _expr_tainted(test, tainted)


def _expr_tainted(node: ast.AST, tainted: set[str]) -> bool:
    """Does the expression read any tainted name (modulo static-attr
    pruning)?"""
    if node is None:
        return False
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_ATTRS:
            # `.shape` etc. of anything is replicated; but we can't easily
            # prune just this subtree from the walk -- handle by checking
            # names NOT under a static attr below.
            continue
    return _names_tainted(node, tainted)


def _names_tainted(node: ast.AST, tainted: set[str]) -> bool:
    """Tainted-name read, skipping subtrees rooted at static attrs."""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Call):
        base = _call_basename(node)
        if base in ("len", "isinstance", "hasattr"):
            return False
        if base in _TAINT_SOURCE_CALLS:
            return True
    return any(_names_tainted(c, tainted) for c in ast.iter_child_nodes(node))


def _assign_taints(value: ast.AST, tainted: set[str]) -> bool:
    """Should an assignment from ``value`` taint its targets?"""
    return _names_tainted(value, tainted)


class _BodyScan:
    """Scan one shard_map body function for conditioned collectives."""

    def __init__(self, mod: ModuleInfo, fn: ast.FunctionDef,
                 inherited_taint: set[str] | None = None,
                 taint_params: bool = True):
        self.mod = mod
        self.fn = fn
        self.tainted: set[str] = set()
        if taint_params:
            # params of a shard_map body (or a fn called from one) are
            # per-shard values.  Builder/driver params are host-replicated
            # config and must NOT be tainted -- only axis_index /
            # process_index introduce divergence there.
            args = fn.args
            self.tainted = {
                a.arg for a in (args.posonlyargs + args.args + args.kwonlyargs)
            }
        if inherited_taint:
            self.tainted |= inherited_taint
        self.findings: list[Finding] = []

    def run(self) -> None:
        self._propagate(self.fn.body)
        self._scan(self.fn.body, hazard_line=None, hazard_cond="")

    # two-phase: first propagate taint through all assignments (fixpoint),
    # then scan control flow with the final taint set
    def _propagate(self, stmts: list[ast.stmt]) -> None:
        changed = True
        while changed:
            changed = False
            for node in ast.walk(self.fn):
                if isinstance(node, ast.Assign):
                    if _assign_taints(node.value, self.tainted):
                        for tgt in node.targets:
                            for n in ast.walk(tgt):
                                if isinstance(n, ast.Name) and \
                                        isinstance(n.ctx, ast.Store) and \
                                        n.id not in self.tainted:
                                    self.tainted.add(n.id)
                                    changed = True
                elif isinstance(node, ast.AugAssign):
                    if _assign_taints(node.value, self.tainted) and \
                            isinstance(node.target, ast.Name) and \
                            node.target.id not in self.tainted:
                        self.tainted.add(node.target.id)
                        changed = True
                elif isinstance(node, ast.For):
                    if _assign_taints(node.iter, self.tainted):
                        for n in ast.walk(node.target):
                            if isinstance(n, ast.Name) and \
                                    n.id not in self.tainted:
                                self.tainted.add(n.id)
                                changed = True

    def _scan(self, stmts: list[ast.stmt], hazard_line: int | None,
              hazard_cond: str) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.If, ast.While)):
                static = _is_static_test(stmt.test, self.tainted)
                line = hazard_line
                cond = hazard_cond
                if not static:
                    line = stmt.lineno
                    cond = ast.unparse(stmt.test)
                self._scan(stmt.body, line, cond)
                self._scan(stmt.orelse, line, cond)
            elif isinstance(stmt, (ast.For, ast.With)):
                self._scan(stmt.body, hazard_line, hazard_cond)
                if isinstance(stmt, ast.For):
                    self._scan(stmt.orelse, hazard_line, hazard_cond)
            elif isinstance(stmt, ast.Try):
                self._scan(stmt.body, hazard_line, hazard_cond)
                for h in stmt.handlers:
                    self._scan(h.body, hazard_line, hazard_cond)
                self._scan(stmt.orelse, hazard_line, hazard_cond)
                self._scan(stmt.finalbody, hazard_line, hazard_cond)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # nested body: inherits outer taint; a collective inside a
                # nested def under a tainted if is still conditioned at
                # the definition site only if *called* there -- we analyze
                # the nested body with inherited taint, rooted at the
                # current hazard region
                sub = _BodyScan(self.mod, stmt, self.tainted)
                sub._propagate(stmt.body)
                sub._scan(stmt.body, hazard_line, hazard_cond)
                self.findings.extend(sub.findings)
            else:
                if hazard_line is not None:
                    self._flag_collectives(stmt, hazard_line, hazard_cond)

    def _flag_collectives(self, stmt: ast.stmt, hazard_line: int,
                          hazard_cond: str) -> None:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                base = _call_basename(node)
                if base in _COLLECTIVES:
                    self.findings.append(Finding(
                        "RPCA-R003", self.mod.display_path, node.lineno,
                        self.mod.qualname(self.fn),
                        f"collective '{base}' reachable under host control "
                        f"flow on non-replicated condition "
                        f"'{hazard_cond}' (line {hazard_line}) -- processes "
                        f"can diverge on which collectives they execute "
                        f"(deadlock / silent corruption on multi-host)",
                    ))


def check(mod: ModuleInfo) -> list[Finding]:
    findings: list[Finding] = []
    bodies = _shard_map_body_names(mod)
    all_fns = {f.name: f for f in mod.functions()}

    # roots: named shard_map bodies (per-shard params => tainted) + any
    # other function containing collectives (driver/builder pattern:
    # params are host-replicated config, so only axis_index /
    # process_index seed taint there)
    roots: dict[int, tuple[ast.FunctionDef, bool]] = {}
    for name in bodies:
        if name in all_fns:
            roots[id(all_fns[name])] = (all_fns[name], True)
    for fn in mod.functions():
        if id(fn) in roots:
            continue
        has_collective = any(
            isinstance(n, ast.Call) and _call_basename(n) in _COLLECTIVES
            for n in ast.walk(fn)
        )
        if has_collective:
            roots[id(fn)] = (fn, False)

    for fn, taint_params in roots.values():
        scan = _BodyScan(mod, fn, taint_params=taint_params)
        scan.run()
        findings.extend(scan.findings)

    # dedup: nested defs inside a root are scanned by the parent walk AND
    # may appear as their own root
    seen: set[tuple[int, str]] = set()
    out: list[Finding] = []
    for f in findings:
        if (f.line, f.message) not in seen:
            seen.add((f.line, f.message))
            out.append(f)
    return out


RULE = Rule(
    id="RPCA-R003",
    name="collective-lockstep",
    doc="no psum/pmean/all-gather under host control flow on non-replicated values",
    check=check,
)
