"""Quickstart: recover a low-rank + sparse decomposition through the
unified ``repro.rpca`` front door.

    PYTHONPATH=src python examples/quickstart.py

One ``solve`` call covers every solver in the stack: ``method="auto"``
picks by problem size and capabilities, explicit methods are drop-in
swaps, and every call returns the same ``RPCAResult`` (components,
factors where the method has them, structured solve stats).
"""
import jax

from repro import rpca
from repro.core import (
    DCFConfig, RunConfig, generate_problem, low_rank_relative_error,
    relative_error,
)
from repro.jax_cache import enable_persistent_cache


def main():
    # A 300x300 matrix of rank 15 with 5% gross corruptions (paper Sec 4.1).
    problem = generate_problem(jax.random.PRNGKey(0), 300, 300, rank=15,
                               sparsity=0.05)

    # 10 simulated clients, each holding 30 columns; consensus on U only.
    cfg = DCFConfig.tuned(rank=15)
    result = rpca.solve(problem.m_obs, method="dcf", cfg=cfg,
                        num_clients=10)

    err = relative_error(result.l, result.s, problem.l0, problem.s0)
    lerr = low_rank_relative_error(result.l, problem.l0)
    print(f"method {result.method}: relative error (Eq. 30) "
          f"{float(err):.2e}, low-rank {float(lerr):.2e}")
    u, v = result.factors
    print(f"consensus factor U: {u.shape}, per-client V: {v.shape}")
    assert err < 1e-4

    # The convex SVD baseline is a drop-in method swap -- same call, same
    # result type (no factors: the convex solvers estimate the rank).
    convex = rpca.solve(problem.m_obs, method="ialm")
    c_err = relative_error(convex.l, convex.s, problem.l0, problem.s0)
    print(f"method {convex.method}: err {float(c_err):.2e}, "
          f"factors: {convex.factors}")

    # method="auto": this problem sits below the SVD-cost threshold, so
    # the exact convex solver wins; a spec with a mesh or num_clients
    # would route to the DCF engines instead.
    auto = rpca.solve(problem.m_obs)
    print(f"auto picked {auto.method!r} "
          f"({int(auto.stats.rounds)} rounds)")

    # Early stopping: run="chunk"/"early" are named runtime presets; pass
    # a RunConfig for custom tolerances.
    early = rpca.solve(problem.m_obs, method="dcf", cfg=cfg, num_clients=10,
                       run=RunConfig(mode="chunk", tol=5e-4, chunk_size=10))
    e_err = relative_error(early.l, early.s, problem.l0, problem.s0)
    print(f"early stop: {int(early.stats.rounds)}/{cfg.outer_iters} rounds, "
          f"err {float(e_err):.2e}")

    # Warm-started refresh: new data + prior factors => a handful of
    # rounds.  result.factors feeds straight back as warm=.  (run="early"
    # is the same mode at the default 1e-6 tolerance.)
    refreshed_m = problem.m_obs + 0.01 * jax.random.normal(
        jax.random.PRNGKey(1), problem.m_obs.shape)
    warm = rpca.solve(refreshed_m, method="dcf", cfg=cfg, num_clients=10,
                      run=RunConfig(mode="while", tol=5e-4),
                      warm=early.factors)
    print(f"warm refresh: {int(warm.stats.rounds)} rounds")


if __name__ == "__main__":
    enable_persistent_cache()
    main()
