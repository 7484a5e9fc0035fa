"""Front-door dispatch overhead: ``rpca.solve`` vs the direct jitted call.

The ``repro.rpca`` facade does Python-level work per solve -- spec
normalization, registry lookup, capability validation -- before hitting
the same jitted program the legacy entrypoints compile.  This bench proves
that work is noise: it times (a) the raw jitted solver implementation,
(b) the front door, and (c) the legacy shim (now routed through the front
door), on a problem small enough that dispatch is a visible fraction of
the solve.

Rows are emitted under stable keys (``api/<name>``) into
``bench_results.json``; the ``overhead_us`` derived column is the
per-solve facade cost and gates CI via ``benchmarks/run.py``, which exits
nonzero on a raised bench (an exception, not a threshold: dispatch
regressions show up in the snapshot diff, hard failures in the gate).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import rpca
from repro.core import DCFConfig, IALMConfig, cf_pca, generate_problem, ialm
from repro.core import runtime as rt
from repro.core.cf_pca import _solve as cf_direct
from repro.core.ialm import _solve as ialm_direct


def _timeit(fn, iters=30):
    jax.block_until_ready(fn().l)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn().l)
    return 1e6 * (time.perf_counter() - t0) / iters  # us/call


def _dispatch_only_us(m_obs, iters=2000):
    """Pure facade cost: time ``solve`` through a no-op registered solver.

    Isolates spec normalization + registry lookup + capability checks +
    result wrapping from any actual compute (the end-to-end rows below are
    dominated by the solve itself and its ~ms timing jitter).
    """
    zeros = jnp.zeros_like(m_obs)
    stats = rt.SolveStats(
        objective=jnp.zeros((1,)), residual=jnp.zeros((1,)),
        rounds=jnp.zeros((), jnp.int32), converged=jnp.ones((), bool),
    )
    rpca.register_solver(
        "bench_noop", rpca.SolverCaps(),
        lambda spec, cfg, run_cfg: (zeros, zeros, None, None, stats),
    )
    try:
        rpca.solve(m_obs, method="bench_noop")  # warm any lazy imports
        t0 = time.perf_counter()
        for _ in range(iters):
            rpca.solve(m_obs, method="bench_noop")
        return 1e6 * (time.perf_counter() - t0) / iters
    finally:
        rpca.SOLVERS.pop("bench_noop", None)


def run(n=96, rank=4, iters=30):
    p = generate_problem(jax.random.PRNGKey(0), n, n, rank, 0.05)
    key = jax.random.PRNGKey(0)
    rows = [{
        "bench": "api_dispatch", "case": "dispatch_only", "n": n,
        "dispatch_us": round(_dispatch_only_us(p.m_obs), 2),
    }]

    cases = [
        (
            "cf",
            DCFConfig.tuned(rank, outer_iters=10),
            lambda cfg: cf_direct(p.m_obs, cfg, key, run=rt.FIXED),
            lambda cfg: rpca.solve(p.m_obs, method="cf", cfg=cfg),
            lambda cfg: cf_pca(p.m_obs, cfg),
        ),
        (
            "ialm",
            IALMConfig(iters=10),
            lambda cfg: ialm_direct(p.m_obs, cfg, run=rt.FIXED),
            lambda cfg: rpca.solve(p.m_obs, method="ialm", cfg=cfg),
            lambda cfg: ialm(p.m_obs, cfg),
        ),
    ]
    for name, cfg, direct, facade, shim in cases:
        t_direct = _timeit(lambda: direct(cfg), iters)
        t_facade = _timeit(lambda: facade(cfg), iters)
        t_shim = _timeit(lambda: shim(cfg), iters)
        rows.append({
            "bench": "api_dispatch", "case": name, "n": n,
            "direct_us": round(t_direct, 1),
            "facade_us": round(t_facade, 1),
            "shim_us": round(t_shim, 1),
            "overhead_us": round(t_facade - t_direct, 1),
            "overhead_frac": round((t_facade - t_direct) / t_direct, 4),
        })
    return rows


def main(full=False):
    rows = run(n=256 if full else 96)
    for r in rows:
        if r["case"] == "dispatch_only":
            print(f"api/dispatch_only,{r['dispatch_us']:.1f},"
                  f"pure facade cost per solve() call")
            continue
        print(f"api/{r['case']}_dispatch,{r['facade_us']:.0f},"
              f"direct_us={r['direct_us']:.0f};shim_us={r['shim_us']:.0f};"
              f"overhead_us={r['overhead_us']:.1f};"
              f"overhead_frac={r['overhead_frac']:.4f}")
    return rows


if __name__ == "__main__":
    main()
