"""Elementary RPCA operators shared by every solver in the framework.

All functions are pure jnp and jit-friendly.  The Pallas kernels in
``repro.kernels`` implement fused versions of the hot paths
(:func:`soft_threshold` of a low-rank residual, and the Huber-clipped
contractions); these are the reference semantics they must match.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def matmul(a: Array, b: Array) -> Array:
    """``a @ b`` at full f32 precision.  On the TPU, XLA's default runs an
    f32 product as one bf16 pass (~2e-3 relative error on ``U V^T``,
    measured on a v5e), which would cap every solver's recovery there."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def soft_threshold(x: Array, lam: Array | float) -> Array:
    """Soft-thresholding (shrinkage) operator: ``sign(x) * max(|x|-lam, 0)``.

    This is the proximal operator of ``lam * ||.||_1`` (paper Eq. 16).
    """
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - lam, 0.0)


def huber_clip(x: Array, lam: Array | float) -> Array:
    """Derivative of the Huber loss ``H_lam`` (paper Eq. 32): clip to [-lam, lam].

    Identity used throughout: ``huber_clip(x, lam) == x - soft_threshold(x, lam)``.
    """
    return jnp.clip(x, -lam, lam)


def huber_loss(x: Array, lam: Array | float) -> Array:
    """Scalar Huber loss ``H_lam`` summed over all entries (paper Eq. 32)."""
    a = jnp.abs(x)
    quad = 0.5 * x * x
    lin = lam * a - 0.5 * lam * lam
    return jnp.sum(jnp.where(a <= lam, quad, lin))


def masked_soft_threshold(x: Array, lam: Array | float, w: Array) -> Array:
    """``W * soft_threshold(x, lam)``: prox of ``lam ||P_Omega(.)||_1``
    restricted to the observed support (S == 0 outside Omega)."""
    return w * soft_threshold(x, lam)


def masked_huber_loss(x: Array, lam: Array | float, w: Array) -> Array:
    """Huber loss summed over *observed* entries only.

    ``H_lam(0) == 0``, so masking the argument masks the contribution; an
    all-ones ``w`` is bit-exact with :func:`huber_loss` (x * 1.0 == x).
    """
    return huber_loss(w * x, lam)


def svt(x: Array, tau: Array | float, full_matrices: bool = False) -> tuple[Array, Array]:
    """Singular-value thresholding: prox of ``tau * ||.||_*``.

    Returns ``(D_tau(x), singular_values_after_threshold)``.  Used only by the
    centralized convex baselines (APGM / IALM) -- the whole point of DCF-PCA is
    to avoid this O(m n min(m,n)) centralized operation.
    """
    u, s, vt = jnp.linalg.svd(x, full_matrices=full_matrices)
    s_shrunk = jnp.maximum(s - tau, 0.0)
    return matmul(u * s_shrunk[..., None, :], vt), s_shrunk


def factored_objective(
    u: Array, v: Array, s: Array, m: Array, rho: float, lam: float,
    w: Array | None = None,
) -> Array:
    """The paper's nonconvex objective, Eq. (4):

    ``1/2 ||U V^T + S - M||_F^2 + rho/2 (||U||_F^2 + ||V||_F^2) + lam ||S||_1``

    With an observation mask ``w`` the data-fit and l1 terms run over
    observed entries only (robust matrix completion).
    """
    resid = matmul(u, v.T) + s - m
    if w is not None:
        resid = w * resid
        s = w * s
    return (
        0.5 * jnp.sum(resid * resid)
        + 0.5 * rho * (jnp.sum(u * u) + jnp.sum(v * v))
        + lam * jnp.sum(jnp.abs(s))
    )


def eliminated_objective(
    u: Array, v: Array, m: Array, rho: float, lam: float,
    w: Array | None = None,
) -> Array:
    """Objective with S eliminated by its closed form (paper Eq. 17):

    ``rho/2 ||V||_F^2 + H_lam(M - U V^T)``   (+ rho/2 ||U||_F^2, added here so
    the value is comparable with :func:`factored_objective` at the optimum).
    With a mask ``w`` the Huber term runs over observed entries only.
    """
    resid = m - matmul(u, v.T)
    if w is not None:
        resid = w * resid
    return (
        huber_loss(resid, lam)
        + 0.5 * rho * (jnp.sum(v * v) + jnp.sum(u * u))
    )


def spectral_norm_ub_gram(g: Array, iters: int = 8) -> Array:
    """``sigma_max^2`` estimate from a precomputed Gram matrix ``G = U^T U``
    via power iteration (r x r, cheap).  Callers that row-shard U psum the
    Gram first so the estimate is global."""
    x = jnp.ones((g.shape[0],), dtype=g.dtype) / jnp.sqrt(g.shape[0])

    def body(_, x):
        y = g @ x
        return y / (jnp.linalg.norm(y) + 1e-30)

    x = jax.lax.fori_loop(0, iters, body, x)
    # Rayleigh quotient after convergence; 1.01 safety factor.
    return 1.01 * (x @ g @ x) / (x @ x)


def spectral_norm_ub(u: Array, iters: int = 8) -> Array:
    """Cheap upper estimate of ``sigma_max(U)^2`` via power iteration on U^T U.

    Used for the inner gradient-descent step size 1/(rho + sigma_max^2);
    the Gram matrix is only r x r so this is O(m r^2 + iters r^2).
    """
    return spectral_norm_ub_gram(u.T @ u, iters)
