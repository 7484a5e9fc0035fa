"""Error arithmetic and the comparison that decides ``correct``."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@jax.jit
def _errors(l, s, l0, s0):
    den = jnp.linalg.norm(l0)
    return (jnp.linalg.norm(l - l0) / den, jnp.linalg.norm(s - s0) / den)


@jax.jit
def _factor_error(u, v, l0):
    l = jnp.matmul(u, v.reshape(-1, v.shape[-1]).T,
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.linalg.norm(l - l0) / jnp.linalg.norm(l0)


def factor_error(u, v, l0) -> float:
    """``||U V^T - L0||_F / ||L0||_F`` from the returned factors (a
    client-stacked ``(E, n_i, r)`` ``V`` is read in column order).  The
    factors are part of the answer -- a refresh solve starts from them --
    and ``U`` is the consensus: one basis for every client's block."""
    return float(_factor_error(u, v, l0))


def recovery_errors(l, s, l0, s0) -> tuple[float, float]:
    """``(||L - L0||_F / ||L0||_F, ||S - S0||_F / ||L0||_F)``.

    Both are measured against the low-rank part's norm: the corruptions
    are ``sqrt(mn)`` large, so a norm of ``S0`` would hide any error of
    the size that matters (the paper's Eq. 30 read 4e-10 on a run whose
    ``L`` was 3e-3 off).
    """
    low, sparse = _errors(l, s, l0, s0)
    return float(low), float(sparse)


@dataclass(frozen=True)
class Check:
    """One number compared with its limit; it passes at or under it."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit  # NaN never passes


def all_ok(checks: list[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)
