"""Plain DCF-PCA (Algorithm 1 of arXiv:2207.11669) in straightforward jnp.

No kernels, no runtime, no batching tricks, and nothing imported from the
program.  ``clients=1`` is centralized CF-PCA.  It follows the schedule of
the front door's presets (annealed threshold, fixed step with Lipschitz
conditioning, alternating-minimization inner solver), as the cell's
workload file states them.

Algorithm 1 starts from a random ``U`` and random ``V_i``.  ``init``
names how the start is drawn from ``key``, as each solver of the front
door draws it (``init_factors``), so that the reference and the program
run the same iteration from the same point: the answer of a fixed
schedule still carries its start at about the size of its recovery
error, and a comparison tight enough to see a broken consensus needs
the same start on both sides.

``precision`` picks how every matrix product is computed:

``"highest"``  f32 at full precision: a second witness that agrees with
               the ground truth where the program does;
``"bf16"``     one bf16 pass with f32 accumulation, the precision below
               the configurations' f32.  This is the control that the
               comparison deciding ``correct`` has to refuse.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _dot(precision: str):
    if precision == "highest":
        return partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        return lambda a, b: jnp.matmul(a.astype(jnp.bfloat16),
                                       b.astype(jnp.bfloat16),
                                       preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def robust_lam(m_obs, mask):
    """``2 * 1.4826 * MAD`` over the observed entries (exact medians)."""
    x = m_obs.ravel()
    if mask is None:
        med = jnp.median(x)
        return 2.0 * 1.4826 * jnp.median(jnp.abs(x - med))
    keep = mask.ravel() > 0
    count = jnp.maximum(jnp.sum(keep.astype(jnp.int32)), 1)

    def median(v):
        vs = jnp.sort(jnp.where(keep, v, jnp.inf))
        return 0.5 * (vs[(count - 1) // 2] + vs[count // 2])

    med = median(x)
    return 2.0 * 1.4826 * median(jnp.abs(x - med))


def _sigma_max_sq(g, dot):
    """Power-iteration bound on the largest eigenvalue of a Gram matrix."""
    x = jnp.ones((g.shape[0], 1), g.dtype) / jnp.sqrt(g.shape[0])
    for _ in range(8):
        y = dot(g, x)
        x = y / (jnp.linalg.norm(y) + 1e-30)
    return 1.01 * jnp.sum(x * dot(g, x)) / jnp.sum(x * x)


def init_factors(key, m: int, nb: int, rank: int, clients: int,
                 init: str):
    """``(U, V)`` with ``V`` stacked ``(clients, nb, rank)``: Gaussian
    entries over ``sqrt(rank)``, drawn from ``key`` as

    ``"cf"``           one solver: ``U`` and ``V`` from the two halves of
                       ``key``;
    ``"dcf"``          simulated clients: ``U`` from the first half of the
                       key's first half, ``V_i`` from the second half of
                       client ``i``'s key, one of ``clients`` keys split
                       from the key's second half;
    ``"dcf_sharded"``  one client a device: ``U`` from the key's first
                       half, ``V_i`` from its second half folded with
                       ``i``.
    """
    scale = 1.0 / jnp.sqrt(jnp.float32(rank))
    normal = lambda k, shape: jax.random.normal(k, shape) * scale
    k_u, k_v = jax.random.split(key)
    if init == "cf":
        return normal(k_u, (m, rank)), normal(k_v, (nb, rank))[None]
    if init == "dcf":
        v = jax.vmap(lambda k: normal(jax.random.split(k)[1], (nb, rank)))(
            jax.random.split(k_v, clients))
        return normal(jax.random.split(k_u)[0], (m, rank)), v
    if init == "dcf_sharded":
        v = jax.vmap(lambda i: normal(jax.random.fold_in(k_v, i),
                                      (nb, rank)))(jnp.arange(clients))
        return normal(k_u, (m, rank)), v
    raise ValueError(f"unknown init {init!r}")


@partial(jax.jit, static_argnames=(
    "rank", "clients", "rounds", "local_iters", "inner_sweeps", "rho",
    "eta", "lam_decay", "lam_min_frac", "precision", "init"))
def solve(m_obs, mask, key, *, rank: int, clients: int, rounds: int,
          local_iters: int, inner_sweeps: int, rho: float, eta: float,
          lam_decay: float, lam_min_frac: float, precision: str,
          init: str):
    """``(L, S)`` of the plain algorithm on ``m_obs`` (hidden entries 0)."""
    dot = _dot(precision)
    m, n = m_obs.shape
    nb = n // clients
    split = lambda a: a.reshape(m, clients, nb).transpose(1, 0, 2)
    blocks = split(m_obs)
    w = jnp.ones_like(blocks) if mask is None else split(mask)
    lam0 = robust_lam(m_obs, mask)
    u, v = init_factors(key, m, nb, rank, clients, init)
    n_frac = 1.0 / clients
    eye = jnp.eye(rank, dtype=jnp.float32)

    def lam_at(t):
        if lam_decay >= 1.0:
            return lam0
        return lam0 * jnp.maximum(lam_decay ** t.astype(jnp.float32),
                                  lam_min_frac)

    def local(u, v, mb, wb, lam):
        def iteration(carry, _):
            u, v = carry
            g = dot(u.T, u)
            cho = jax.scipy.linalg.cho_factor(g + rho * eye)

            def sweep(v, _):
                psi = wb * jnp.clip(mb - dot(u, v.T), -lam, lam)
                rhs = dot(g, v.T) + dot(u.T, psi)
                return jax.scipy.linalg.cho_solve(cho, rhs).T, None

            v, _ = jax.lax.scan(sweep, v, None, length=inner_sweeps)
            psi = wb * jnp.clip(mb - dot(u, v.T), -lam, lam)
            grad = -dot(psi, v) + n_frac * rho * u
            lip = _sigma_max_sq(dot(v.T, v), dot) + n_frac * rho
            return (u - (eta / lip) * grad, v), None

        (u, v), _ = jax.lax.scan(iteration, (u, v), None,
                                 length=local_iters)
        return u, v

    def one_round(carry, t):
        u, v = carry
        u_i, v = jax.vmap(local, in_axes=(None, 0, 0, 0, None))(
            u, v, blocks, w, lam_at(t))
        return (jnp.mean(u_i, axis=0), v), None

    (u, v), _ = jax.lax.scan(one_round, (u, v), jnp.arange(rounds))
    l = jax.vmap(lambda vb: dot(u, vb.T))(v)
    r = blocks - l
    s = w * jnp.sign(r) * jnp.maximum(jnp.abs(r) - lam_at(
        jnp.asarray(rounds - 1)), 0.0)
    merge = lambda a: a.transpose(1, 0, 2).reshape(m, n)
    return merge(l), merge(s)
