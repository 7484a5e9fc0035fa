"""The altmin V-step applies ``(G + rho I)^{-1}`` as a product formed once
per U (``factorized._altmin_ctx``).

Each sweep solves ``(G + rho I) V^T = G V^T + C^T`` for the new V.  The
back-substitution form below (``cho_solve`` against the Cholesky factor,
every sweep) is the reference: the inverse form must agree with it to f32
rounding, scaled by the system's condition number, and must be no less
accurate than it against a float64 solve.  The program-structure test
pins where the factorization and the triangular solves run: once per
local iteration, never inside the sweep scan.
"""
import os
import subprocess
import sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from repro.core import factorized as fz
from repro.core import ops as core_ops
from repro.kernels import ops as kops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EPS = float(np.finfo(np.float32).eps)
M, N, RANK, RHO, LAM, SWEEPS, CLIENTS = 96, 40, 6, 1e-2, 0.3, 3, 16


def _cho_update(u, rho):
    """The sweep as a back-substitution against the factored system."""
    g = core_ops.matmul(u.T, u)
    cho = jax.scipy.linalg.cho_factor(g + rho * jnp.eye(g.shape[0]))
    return lambda v, contr: jax.scipy.linalg.cho_solve(
        cho, core_ops.matmul(g, v.T) + contr.T).T


def _cho_altmin(u, v, m_blk, rho, lam, sweeps, w=None):
    update = _cho_update(u, rho)
    for _ in range(sweeps):
        v = update(v, kops.huber_contract_v(u, v, m_blk, lam, w=w))
    return v


def _factors_with_cond(cond: float, seed: int = 0):
    """U (m, r) whose ``G + rho I`` has condition number ``cond``, and a
    V and a contraction of the scale a sweep sees."""
    rng = np.random.default_rng(seed)
    q1, _ = np.linalg.qr(rng.standard_normal((M, RANK)))
    q2, _ = np.linalg.qr(rng.standard_normal((RANK, RANK)))
    eig = np.geomspace(1.0, cond, RANK) * 4.0  # eigenvalues of G + rho I
    u = (q1 * np.sqrt(eig - RHO)) @ q2
    v = rng.standard_normal((N, RANK))
    contr = 5.0 * rng.standard_normal((N, RANK))
    return u, v, contr


def _problem(seed: int, observed: float | None):
    """A rank-r plane with 5% gross corruption, its (U, V) start and,
    when ``observed`` is given, a uniform 0/1 mask."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l0 = (jax.random.normal(ks[0], (M, RANK))
          @ jax.random.normal(ks[1], (RANK, N)))
    spikes = jax.random.uniform(ks[2], (M, N)) < 0.05
    m_obs = l0 + jnp.where(spikes, 10.0 * jnp.sign(
        jax.random.normal(ks[3], (M, N))), 0.0)
    state = fz.init_state(ks[4], M, N, RANK)
    w = None
    if observed is not None:
        w = (jax.random.uniform(ks[5], (M, N)) < observed).astype(
            jnp.float32)
    return m_obs, state.u, state.v, w


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _single_update(cond):
    u, v, contr = _factors_with_cond(cond)
    a = u.T @ u + RHO * np.eye(RANK)
    exact = np.linalg.solve(a, u.T @ u @ v.T + contr.T).T
    u32, v32, c32 = (jnp.asarray(x, jnp.float32) for x in (u, v, contr))
    _, update = fz._altmin_ctx(u32, RHO)
    got = update(v32, c32)
    want = _cho_update(u32, RHO)(v32, c32)
    assert np.isclose(np.linalg.cond(a), cond, rtol=1e-3)
    tol = 4.0 * EPS * cond
    assert _rel(got, want) <= tol, (_rel(got, want), tol)
    # No less accurate than the back-substitution against float64.
    assert _rel(got, exact) <= max(_rel(want, exact), 4.0 * EPS) * 1.5


def _inner_solve(observed):
    m_obs, u, v, w = _problem(3, observed)
    got = fz.inner_solve_altmin(u, v, m_obs, RHO, LAM, SWEEPS, "auto", w=w)
    want = _cho_altmin(u, v, m_obs, RHO, LAM, SWEEPS, w=w)
    assert _rel(got, want) < 1e-5, _rel(got, want)


def _vmapped_clients():
    """16 clients, each its own U, V, block and mask, as the simulated
    engine maps ``local_round`` over them."""
    parts = [_problem(10 + i, 0.8) for i in range(CLIENTS)]
    m_obs, u, v, w = (jnp.stack(x) for x in zip(*parts))
    got = jax.vmap(lambda u, v, m, w: fz.inner_solve_altmin(
        u, v, m, RHO, LAM, SWEEPS, "auto", w=w))(u, v, m_obs, w)
    want = jax.vmap(lambda u, v, m, w: _cho_altmin(
        u, v, m, RHO, LAM, SWEEPS, w=w))(u, v, m_obs, w)
    for i in range(CLIENTS):
        assert _rel(got[i], want[i]) < 1e-5, (i, _rel(got[i], want[i]))


def _psum_rows():
    """U row-sharded over a four-device "model" axis: the Gram and the
    contraction are psum'd, and every shard applies the same inverse."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent(f"""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.core import factorized as fz
        from tests.test_ridge_update import _cho_altmin, _problem, _rel
        m_obs, u, v, w = _problem(7, 0.8)
        mesh = jax.make_mesh((4,), ("model",))
        psum = lambda x: jax.lax.psum(x, "model")
        solve = jax.shard_map(
            lambda u, m, w: fz.inner_solve_altmin(
                u, v, m, {RHO}, {LAM}, {SWEEPS}, "auto",
                reduce_m=psum, w=w),
            mesh=mesh, in_specs=(P("model"), P("model"), P("model")),
            out_specs=P(), check_vma=False)
        got = jax.jit(solve)(u, m_obs, w)
        want = _cho_altmin(u, v, m_obs, {RHO}, {LAM}, {SWEEPS}, w=w)
        print("REL", _rel(got, want))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    rel = float(out.stdout.split("REL")[-1])
    assert rel < 1e-5, rel


CASES = {
    "cond1": partial(_single_update, 1.0),
    "cond1e2": partial(_single_update, 1e2),
    "cond1e4": partial(_single_update, 1e4),
    "unmasked": partial(_inner_solve, None),
    "masked": partial(_inner_solve, 0.8),
    "16_vmapped_clients": _vmapped_clients,
    "psum_reduce_m": _psum_rows,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_inverse_update_matches_back_substitution(case):
    CASES[case]()


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for item in val if isinstance(val, (tuple, list)) else (val,):
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _count(jaxpr, prim: str) -> int:
    return sum(e.primitive.name == prim for e in _eqns(jaxpr))


def _scans(jaxpr):
    """The outermost scans reachable from ``jaxpr``, with their bodies."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["jaxpr"].jaxpr
        else:
            for sub in _sub_jaxprs(eqn):
                yield from _scans(sub)


@pytest.mark.parametrize("fused", ["diag", "dual"])
def test_no_factorization_or_solve_inside_the_sweeps(fused):
    """The vmapped round: one Cholesky factorization and at most two
    triangular solves a local iteration, none in any sweep."""
    cfg = fz.DCFConfig.masked(RANK, fused=fused)
    parts = [_problem(20 + i, 0.8) for i in range(CLIENTS)]
    m_obs, _, v, w = (jnp.stack(x) for x in zip(*parts))
    u = parts[0][1]
    round_fn = jax.vmap(
        lambda v, m, w: fz.local_round(u, v, m, cfg=cfg, lam=LAM,
                                       n_frac=1.0 / CLIENTS,
                                       eta=jnp.float32(0.5), w=w))
    jaxpr = jax.make_jaxpr(round_fn)(v, m_obs, w).jaxpr
    [local_iter] = list(_scans(jaxpr))  # the K local iterations
    sweeps = list(_scans(local_iter))
    assert sweeps, "no sweep scan inside the local iteration"
    for body in sweeps:
        assert _count(body, "triangular_solve") == 0
        assert _count(body, "cholesky") == 0
    assert _count(local_iter, "cholesky") == 1
    assert 1 <= _count(local_iter, "triangular_solve") <= 2
