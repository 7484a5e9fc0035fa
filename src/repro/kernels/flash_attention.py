"""Pallas TPU flash-attention (forward): tiled online-softmax attention.

Identified by the roofline analysis (EXPERIMENTS.md Sec. 3) as the lever
for the memory term of every dense prefill cell: the jax-level chunked
attention writes (B, H, q_chunk, S) f32 score blocks to HBM; this kernel
keeps them in VMEM with the standard running-max/running-sum recurrence,
so HBM traffic drops to reading Q/K/V and writing O.

Grid: (B*H, S_q/bq) parallel x (S_kv/bk) arbitrary (the online-softmax
reduction).  Scratch carries the f32 accumulator + running stats across
the kv axis.  Causal masking via absolute row/col indices; fully-masked
key blocks are skipped by the grid when causal (block-triangular skip).

Serving-scoped: forward only (prefill / decode have no backward); training
continues to use the chunked-jnp path, whose backward is exercised by the
remat policy.  Validated against the jnp oracle in interpret mode
(tests/test_flash_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat

Array = jax.Array

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  kv_steps: int, skv_real: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_i = pl.program_id(1)
    row0 = q_i * bq
    col0 = kv_i * bk

    # Skip key blocks strictly above the diagonal when causal.
    run = (not causal) or (col0 <= row0 + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols < skv_real, s, NEG_INF)  # zero-padded K cols
        if causal:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_cur = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=1))[:, None]
        alpha = jnp.exp(m_prev - m_cur)  # (bq, 1)
        p = jnp.exp(s - m_cur)  # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(kv_i == kv_steps - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "bq", "bk", "interpret"),
)
def flash_attention(
    q: Array,  # (B, S_q, H, d)  -- GQA pre-expanded to H heads
    k: Array,  # (B, S_kv, H, d)
    v: Array,  # (B, S_kv, H, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
) -> Array:
    """Returns (B, S_q, H, d) in q.dtype.  S_q/S_kv are padded to the block
    size internally; padded key columns are masked in-kernel (skv_real)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    bq_ = min(bq, max(sq, 8))
    bk_ = min(bk, max(skv, 8))

    pad_q = (-sq) % bq_
    pad_k = (-skv) % bk_
    # K/V zero-padding is masked in-kernel via the skv_real column bound.
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    # (B, S, H, d) -> (B*H, S, d)
    def bh(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])

    qb, kb, vb = bh(q), bh(k), bh(v)
    n_bh = qb.shape[0]
    q_steps = qb.shape[1] // bq_
    kv_steps = kb.shape[1] // bk_

    interpret = compat.should_interpret(interpret)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq_, bk=bk_,
        kv_steps=kv_steps, skv_real=skv)
    out = pl.pallas_call(
        kernel,
        grid=(n_bh, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, bk_, d), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, bk_, d), lambda b_, i, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qb.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, d), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qb, kb, vb)

    out = out.reshape(b, h, q.shape[1], d).transpose(0, 2, 1, 3)
    return out[:, :sq]
