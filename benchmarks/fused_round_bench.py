"""Fused-round benchmark: the bandwidth-optimal solve path vs the PR-4 path.

Compares, on the masked 2048 x 2048 rank-64 benchmark problem (E=4
clients, the ISSUE-5 acceptance configuration; ``--fast`` shrinks it for
smoke runs):

``pr4``    the unfused path -- f32 data plane, dense f32 mask,
           ``fused="off"`` (J sweeps + separate U-step contraction per
           local iteration) and the separate per-round objective pass;
``fused``  the bandwidth-optimal path -- ``fused="dual"`` (the final inner
           sweep is the dual-contraction kernel whose epilogue also emits
           the round diagnostics), bf16 data plane, bit-packed mask.

Three metric families per path:

* ``round_ms``          marginal wall-clock per consensus round, measured
                        as the difference of two fixed-budget solves (the
                        per-solve setup cancels); the ratio is
                        ``round_wall_speedup``.
* ``hbm_bytes_round``   the modelled HBM bytes one round must stream
                        (data + mask reads per pass x passes per round +
                        diagnostics passes) -- deterministic, and the
                        quantity the fusion actually optimizes; the ratio
                        is ``hbm_bytes_speedup``.  On a bandwidth-bound
                        accelerator wall-clock tracks this model; on a
                        small-host CPU the round is gemm-FLOP-bound and
                        the measured wall ratio is closer to the pass-count
                        ratio (the bench prints both, honestly).
* ``e2e_ms``            end-to-end refresh-style solve (20 rounds incl.
                        problem construction): the fused path also
                        calibrates lam on a 64k-entry subsample instead of
                        two full-matrix medians.

Quality gates ride along: the f32 fused kernels are bit-exact vs the
unfused ref oracles (asserted here), and the bf16 path's recovery error
must stay within 5x of f32 on the seed recovery problem.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses
import importlib

from repro.core import factorized as fz
from repro.core import problems as prob
from repro.core import runtime as rt
from repro.core.metrics import relative_error
from repro.kernels import ref

# repro.core re-exports dcf_pca/cf_pca as *functions*; the modules are
# what we need for make_problem/make_solver.
dcf = importlib.import_module("repro.core.dcf_pca")
cf = importlib.import_module("repro.core.cf_pca")

F32, BF16, U8 = 4, 2, 1


def _bytes_per_round(cfg: fz.DCFConfig, m: int, n: int,
                     data_bytes: int, mask_bytes: float,
                     separate_obj: bool) -> float:
    """Modelled HBM bytes streamed per consensus round (data + mask reads
    per full-matrix pass; the skinny factor traffic is negligible)."""
    per_pass = m * n * (data_bytes + mask_bytes)
    if cfg.fused == "dual":
        passes = cfg.local_iters * cfg.inner_sweeps
    else:
        passes = cfg.local_iters * (cfg.inner_sweeps + 1)
    if separate_obj:
        passes += 1
    return passes * per_pass


def _marginal_round_ms(make_cfg, solve, t_short=4, t_long=24, reps=5):
    """Median marginal wall-clock per round across interleaved repeats."""
    fns = {}
    for t in (t_short, t_long):
        fns[t] = solve(make_cfg(t))
        fns[t]()  # compile + warm
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter(); fns[t_short](); ta = time.perf_counter() - t0
        t0 = time.perf_counter(); fns[t_long](); tb = time.perf_counter() - t0
        samples.append((tb - ta) / (t_long - t_short) * 1e3)
    samples.sort()
    return samples[len(samples) // 2]


def run(m=2048, n=2048, rank=64, clients=4, observed=0.7):
    key = jax.random.PRNGKey(0)
    p = prob.generate_problem(key, m, n, rank, 0.1, observed_frac=observed)

    # -- bit-exactness gate: f32 fused kernels vs unfused ref oracles ------
    ku, kv = jax.random.split(jax.random.PRNGKey(1))
    us = jax.random.normal(ku, (256, 32))
    vs = jax.random.normal(kv, (192, 32))
    ms = p.m_obs[:256, :192]
    ws = p.mask[:256, :192]
    cv, cu, _, _ = ref.huber_dual_contract_masked(us, vs, ms, ws, 0.9)
    assert np.array_equal(
        np.asarray(cv),
        np.asarray(ref.huber_contract_v_masked(us, vs, ms, ws, 0.9)),
    ), "fused ref oracle diverged from unfused composition"
    assert np.array_equal(
        np.asarray(cu),
        np.asarray(ref.huber_contract_u_masked(us, vs, ms, ws, 0.9)),
    )

    base = dict(rank=rank, local_iters=2, inner_sweeps=3, rho=1e-2,
                eta0=0.5, lr_schedule="fixed", lam_decay=0.97,
                track_objective=True)

    cfg_pr4 = fz.DCFConfig(outer_iters=4, fused="off", **base)
    cfg_fused = fz.DCFConfig(outer_iters=4, fused="dual", pack_mask=True,
                             **base)

    problem_pr4 = dcf.make_problem(p.m_obs, cfg_pr4, clients, key,
                                   mask=p.mask)
    problem_fused = dcf.make_problem(
        p.m_obs.astype(jnp.bfloat16), cfg_fused, clients, key, mask=p.mask
    )

    def solve_factory(problem):
        def solve(cfg):
            solver = dcf.make_solver(cfg, with_objective=True)
            f = jax.jit(
                lambda pr: rt.run(solver, pr, cfg.outer_iters, rt.FIXED)[0].u
            )
            return lambda: f(problem).block_until_ready()
        return solve

    def cfg_at(template):
        return lambda t: dataclasses.replace(template, outer_iters=t)

    pr4_ms = _marginal_round_ms(cfg_at(cfg_pr4), solve_factory(problem_pr4))
    fused_ms = _marginal_round_ms(cfg_at(cfg_fused),
                                  solve_factory(problem_fused))

    pr4_bytes = _bytes_per_round(cfg_pr4, m, n, F32, F32, separate_obj=True)
    fused_bytes = _bytes_per_round(cfg_fused, m, n, BF16, U8 / 8.0,
                                   separate_obj=False)

    # -- end-to-end refresh-style solve (20 rounds incl. construction) -----
    t_e2e = 20

    def e2e(cfg, mat, lam_sample):
        # The compact path calibrates lam on a ~64k-entry strided
        # subsample instead of two full-matrix medians (DCFConfig.lam_sample
        # -- inside the timed program, so both sides pay their own
        # calibration).
        cfg = dataclasses.replace(
            cfg, outer_iters=t_e2e,
            lam_sample=(1 << 16) if lam_sample else None,
        )

        @jax.jit
        def run_once(mat_in):
            problem = dcf.make_problem(mat_in, cfg, clients, key,
                                       mask=p.mask)
            solver = dcf.make_solver(cfg, with_objective=True)
            carry, _ = rt.run(solver, problem, cfg.outer_iters, rt.FIXED)
            return carry.u

        run_once(mat).block_until_ready()  # compile
        t0 = time.perf_counter()
        run_once(mat).block_until_ready()
        return (time.perf_counter() - t0) * 1e3

    e2e_pr4 = e2e(cfg_pr4, p.m_obs, lam_sample=False)
    e2e_fused = e2e(cfg_fused, p.m_obs.astype(jnp.bfloat16),
                    lam_sample=True)

    # -- bf16 recovery-quality gate on the (smaller) seed recovery shape ---
    ps = prob.generate_problem(jax.random.PRNGKey(0), 96, 96, 4, 0.05)
    # Both sides run fused="dual" so the gate isolates the bf16 data plane
    # (comparing dual-bf16 against diag-f32 would conflate the stale-
    # gradient semantics of "dual" with storage precision).
    cfg_q = dataclasses.replace(fz.DCFConfig.tuned(4, outer_iters=120),
                                fused="dual")
    r32 = _quality(ps, cfg_q, jnp.float32)
    r16 = _quality(ps, cfg_q, jnp.bfloat16)
    bf16_ok = r16 < max(5.0 * r32, 2e-2)
    if not bf16_ok:
        # Surfaces through ``run.py`` as a failed bench: the
        # compact data plane must never cost more than 5x recovery error.
        raise AssertionError(
            f"bf16 recovery error {r16:.3g} exceeds 5x f32 ({r32:.3g})"
        )

    rows = [
        {"bench": "fused_round", "name": "pr4_round", "ms": pr4_ms,
         "hbm_bytes": pr4_bytes},
        {"bench": "fused_round", "name": "fused_round", "ms": fused_ms,
         "hbm_bytes": fused_bytes},
        {"bench": "fused_round", "name": "speedups",
         "round_wall_speedup": pr4_ms / fused_ms,
         "hbm_bytes_speedup": pr4_bytes / fused_bytes,
         "e2e20_speedup": e2e_pr4 / e2e_fused,
         "e2e_pr4_ms": e2e_pr4, "e2e_fused_ms": e2e_fused},
        {"bench": "fused_round", "name": "quality",
         "recovery_err_f32": r32, "recovery_err_bf16": r16,
         "bf16_within_5x": bool(bf16_ok)},
    ]
    return rows


def _quality(p, cfg, dtype):
    r = cf._solve(p.m_obs.astype(dtype), cfg, jax.random.PRNGKey(0),
                  run=rt.FIXED)
    return float(relative_error(r.l, r.s, p.l0, p.s0))


def main(full=False, fast=None):
    # The acceptance configuration is the default; JAX_PLATFORMS=cpu CI
    # boxes handle it in ~2 min.  ``fast`` (or RPCA_BENCH_FAST=1) shrinks.
    import os

    if fast is None:
        fast = os.environ.get("RPCA_BENCH_FAST", "") == "1"
    rows = run(m=512, n=512, rank=16) if fast else run()
    for r in rows:
        extras = {k: v for k, v in r.items() if k not in ("bench", "name")}
        print(f"fused_round/{r['name']},"
              + ",".join(f"{k}={v:.4g}" if isinstance(v, float) else
                         f"{k}={v}" for k, v in extras.items()))
    return rows


if __name__ == "__main__":
    main()
