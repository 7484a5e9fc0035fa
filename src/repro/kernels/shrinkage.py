"""Fused low-rank-residual soft-threshold kernel.

    S = soft_threshold(M - U V^T, lam)        (paper Eq. 16)

One pass: each (bm, bn) tile computes its slice of U V^T on the MXU and
applies the shrinkage epilogue while the tile is still in VMEM -- the
residual itself never round-trips through HBM.  Optionally also emits
``Psi = clip(M - U V^T, +-lam) = residual - S`` from the same tile (used
when the caller wants both the sparse estimate and the Huber derivative,
e.g. the final DCF-PCA output step).

Compact data plane: a bfloat16 ``M`` is upcast per-tile (every epilogue
computes in f32 -- see the ``.astype(jnp.float32)`` on the data tile).
Bit-packed masks are unpacked once at the ``kernels.ops`` dispatch layer
before reaching these kernels: shrinkage runs once per *solve* (the
finalize step), not per round, so its mask traffic is not on the
steady-state path the packed plane optimizes (DESIGN.md Sec. 12).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.huber_contract import (
    _LAM_SPEC,
    DEFAULT_BM,
    DEFAULT_BN,
    LANE,
    _lam_operand,
    _mxu,
    _pad_to,
    _should_interpret,
)

Array = jax.Array


def _shrink_kernel(u_ref, v_ref, m_ref, lam_ref, s_ref):
    lam = lam_ref[0, 0]
    low = _mxu(u_ref[...], v_ref[...].T)
    r = m_ref[...].astype(jnp.float32) - low
    s_ref[...] = jnp.sign(r) * jnp.maximum(jnp.abs(r) - lam, 0.0)


def _shrink_psi_kernel(u_ref, v_ref, m_ref, lam_ref, s_ref, psi_ref):
    lam = lam_ref[0, 0]
    low = _mxu(u_ref[...], v_ref[...].T)
    r = m_ref[...].astype(jnp.float32) - low
    s = jnp.sign(r) * jnp.maximum(jnp.abs(r) - lam, 0.0)
    s_ref[...] = s
    psi_ref[...] = r - s


def _shrink_masked_kernel(u_ref, v_ref, m_ref, w_ref, lam_ref, s_ref):
    lam = lam_ref[0, 0]
    low = _mxu(u_ref[...], v_ref[...].T)
    r = m_ref[...].astype(jnp.float32) - low
    s_ref[...] = w_ref[...].astype(jnp.float32) * (
        jnp.sign(r) * jnp.maximum(jnp.abs(r) - lam, 0.0)
    )


def _shrink_psi_masked_kernel(u_ref, v_ref, m_ref, w_ref, lam_ref, s_ref,
                              psi_ref):
    lam = lam_ref[0, 0]
    low = _mxu(u_ref[...], v_ref[...].T)
    w = w_ref[...].astype(jnp.float32)
    r = m_ref[...].astype(jnp.float32) - low
    s = w * (jnp.sign(r) * jnp.maximum(jnp.abs(r) - lam, 0.0))
    s_ref[...] = s
    psi_ref[...] = w * r - s


def _specs(bm: int, bn: int, r_pad: int):
    return [
        pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)),
        pl.BlockSpec((bn, r_pad), lambda i, j: (j, 0)),
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        _LAM_SPEC,
    ]


def _specs_masked(bm: int, bn: int, r_pad: int):
    return [
        pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)),
        pl.BlockSpec((bn, r_pad), lambda i, j: (j, 0)),
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        _LAM_SPEC,
    ]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def residual_shrink(
    u: Array,
    v: Array,
    m: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """S = soft_threshold(M - U V^T, lam), shape (m, n), f32."""
    mm, n = m.shape
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[0] // bm, m_p.shape[1] // bn)
    s = pl.pallas_call(
        _shrink_kernel,
        grid=grid,
        in_specs=_specs(bm, bn, r_pad),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(m_p.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, lam_arr)
    return s[:mm, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def residual_shrink_psi(
    u: Array,
    v: Array,
    m: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """(S, Psi) from one pass; Psi = (M - U V^T) - S = clip(residual, +-lam)."""
    mm, n = m.shape
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[0] // bm, m_p.shape[1] // bn)
    s, psi = pl.pallas_call(
        _shrink_psi_kernel,
        grid=grid,
        in_specs=_specs(bm, bn, r_pad),
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(m_p.shape, jnp.float32),
            jax.ShapeDtypeStruct(m_p.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, lam_arr)
    return s[:mm, :n], psi[:mm, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def residual_shrink_masked(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """S = W * soft_threshold(M - U V^T, lam): sparse estimate on observed
    entries only (S is identically 0 outside Omega)."""
    mm, n = m.shape
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    w_p = _pad_to(_pad_to(w, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[0] // bm, m_p.shape[1] // bn)
    s = pl.pallas_call(
        _shrink_masked_kernel,
        grid=grid,
        in_specs=_specs_masked(bm, bn, r_pad),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(m_p.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, w_p, lam_arr)
    return s[:mm, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def residual_shrink_psi_masked(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """(S, Psi) masked: S = W * soft_threshold(M - U V^T, lam),
    Psi = W * clip(M - U V^T, +-lam), both from one tile pass."""
    mm, n = m.shape
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    w_p = _pad_to(_pad_to(w, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[0] // bm, m_p.shape[1] // bn)
    s, psi = pl.pallas_call(
        _shrink_psi_masked_kernel,
        grid=grid,
        in_specs=_specs_masked(bm, bn, r_pad),
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(m_p.shape, jnp.float32),
            jax.ShapeDtypeStruct(m_p.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, w_p, lam_arr)
    return s[:mm, :n], psi[:mm, :n]
