"""RPCA-R006 — consensus-dispatch.

Invariant (PR 10): every consensus boundary -- the point in a solver
step where per-client factor payloads (``u_i`` / ``v_i`` stacks or
shards) combine into the shared iterate -- must route through the one
consensus dispatch, ``factorized.aggregate_sharded``, which the DCF round
step (``dcf_pca.client_step``) calls per client over the named client
axis under ``vmap`` or ``shard_map``.  A raw
``jnp.mean(u_i, axis=0)`` / ``lax.pmean(u_i, axes)`` / ``psum(u_i, ...)``
hand-rolls the weighted mean at one boundary and silently ignores
``DCFConfig.aggregator`` / ``divergence_screen`` there: Byzantine
robustness that "works" everywhere except the one path a refactor
reintroduced is exactly the kind of regression a test sample misses.

Heuristic (conservative -- skip, don't guess):

* only calls whose final attribute is ``mean`` / ``pmean`` / ``psum``;
* only when the first positional argument is a plain name starting with
  ``u`` or ``v`` (the factor-payload naming convention of the DCF
  engines; ``psum(contrib, ...)``, ``psum(raw_w, ...)``,
  ``psum(1.0, "clients")`` and friends never trip);
* only inside functions whose qualname contains ``step`` (the solver
  round bodies) -- the blessed aggregators themselves (``aggregate_*``)
  and setup/finalize code are out of scope.
"""
from __future__ import annotations

import ast

from tools.analysis.core import Finding, ModuleInfo, Rule, dotted_name

_COMBINERS = ("mean", "pmean", "psum")


def _first_arg_is_factor(call: ast.Call) -> bool:
    if not call.args:
        return False
    a0 = call.args[0]
    return isinstance(a0, ast.Name) and a0.id[:1] in ("u", "v")


def check(mod: ModuleInfo) -> list[Finding]:
    findings: list[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted_name(node.func) or ""
        leaf = d.split(".")[-1]
        if leaf not in _COMBINERS:
            continue
        if not _first_arg_is_factor(node):
            continue
        qual = mod.qualname(node)
        fn = qual.split(".")[-1]
        if "step" not in fn.lower():
            continue  # not a solver round body
        if fn.startswith("aggregate"):
            continue  # the dispatch itself is the one blessed site
        if mod.noqa(node.lineno, "RPCA-R006"):
            continue
        payload = node.args[0].id  # type: ignore[union-attr]
        findings.append(Finding(
            "RPCA-R006", mod.display_path, node.lineno, qual,
            f"raw {leaf}({payload}, ...) combines client factor payloads "
            f"inside a solver step: route this consensus boundary through "
            f"factorized.aggregate_sharded so "
            f"DCFConfig.aggregator and the divergence screen apply here "
            f"too",
        ))
    return findings


RULE = Rule(
    id="RPCA-R006",
    name="consensus-dispatch",
    doc="solver steps must combine client factors via the aggregator "
        "dispatch, never a raw mean/pmean/psum",
    check=check,
)
