"""DCF-PCA robust gradient aggregation (the paper's technique as a
first-class data-parallel feature -- DESIGN.md Sec. 3).

In data-parallel training, worker i's weight-gradient matrix ``G_i`` (m, k)
is one column block of the paper's distributed data matrix
``M = [G_1 ... G_E]``.  Running a few DCF-PCA consensus rounds yields

    G_i ~= U V_i^T + S_i,   U consensual (m, r),  V_i/S_i local,

and the aggregate used by the optimizer is the *robust* mean

    mean_i G_i ~= U (mean_i V_i)^T        (sparse outliers S_i rejected)

Communication per round: one pmean of U (m r) + one final pmean of V (k r)
-- the paper's 2 E m r bound -- versus m k for a plain all-reduce.  The
sparse residual absorbs gross per-worker corruption (bit-flips, poisoned
shards, fp overflow on a straggler), which plain averaging propagates.

``aggregate_tree`` applies this to every stacked 2-D weight leaf (3-D
(L, m, k) leaves are vmapped) and falls back to plain pmean for small /
1-D leaves.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import factorized as fz
from repro.core.dcf_pca import client_init, client_step

Array = jax.Array


@dataclass(frozen=True)
class CompressConfig:
    rank: int = 8
    rounds: int = 4  # consensus rounds T
    local_iters: int = 1  # K
    inner_sweeps: int = 2  # J
    rho: float = 1e-3
    lam_mult: float = 2.5  # threshold = lam_mult * robust sigma
    eta: float = 0.5
    min_dim: int = 64  # leaves smaller than this skip compression
    #: Ship only the top-k fraction of each consensus U delta (with an
    #: error-feedback residual); ``None`` keeps the dense factor wire.
    topk_frac: float | None = None

    def dcf(self) -> fz.DCFConfig:
        """The DCF round of one aggregation; ``topk_frac`` sets its wire."""
        return fz.DCFConfig(
            rank=self.rank, outer_iters=self.rounds,
            local_iters=self.local_iters, inner_sweeps=self.inner_sweeps,
            rho=self.rho, eta0=self.eta, lr_schedule="fixed",
            precondition="lipschitz", impl="ref",
            consensus_compress=None if self.topk_frac is None else self,
        )


def _robust_sigma(g: Array, axes, eps: float = 1e-6) -> Array:
    """Robust scale of a gradient leaf, floored away from zero.

    The plain MAD collapses to 0 on mostly-zero leaves (embedding rows,
    expert shards, post-warmup sparse grads: > 50% exact zeros), which
    would set ``lam = 0`` so the sparse term absorbs the *entire* gradient
    and the robust aggregate silently returns ~0.  When that happens, fall
    back to the MAD over the **nonzero** deviations -- the robust scale of
    the leaf's support, still immune to a minority of gross outliers among
    the active entries (a naive ``eps * rms`` floor is not: one corrupted
    worker's 1e4-scale spikes inflate its rms by orders of magnitude).
    The tiny ``eps * rms`` term only rescues fully-constant leaves where
    even the support is empty.
    """
    med = jnp.median(g)
    dev = jnp.abs(g - med).ravel()
    # One sort serves both medians (this runs per gradient leaf per step):
    # the zeros sit at the front of the sorted deviations, so the median
    # over the nonzero support is just an offset into the same array.
    x = jnp.sort(dev)
    sz = dev.size
    mad = 0.5 * (x[(sz - 1) // 2] + x[sz // 2])
    cnt = jnp.maximum(jnp.sum(dev > 0), 1)
    z = sz - cnt
    mad_nz = 0.5 * (x[z + (cnt - 1) // 2] + x[z + cnt // 2])
    rms = jnp.sqrt(jnp.mean(jnp.square(g)))
    sigma = jnp.where(mad > 0, mad, mad_nz)
    return jax.lax.pmean(jnp.maximum(1.4826 * sigma, eps * rms), axes)


def consensus_compress(
    g_local: Array,  # (m, k) this worker's gradient
    axes,  # mesh axis name(s) of the DP dimension
    ccfg: CompressConfig,
    key: Array,
) -> Array:
    """Robust aggregate of a 2-D gradient leaf across the DP axes: the DCF
    round (:func:`client_step`) scanned ``ccfg.rounds`` times, each
    worker one client."""
    cfg = ccfg.dcf()
    lam = ccfg.lam_mult * _robust_sigma(g_local, axes) + 1e-12

    # Sketch init: U0 = pmean(G_i Omega) -- one power-iteration step toward
    # the dominant shared column space (Omega shared via the common key).
    omega = jax.random.normal(key, (g_local.shape[1], ccfg.rank),
                              jnp.float32)
    u = jax.lax.pmean(g_local.astype(jnp.float32) @ omega, axes)
    u = u / (jnp.linalg.norm(u, axis=0, keepdims=True) + 1e-12)
    v = jnp.zeros((g_local.shape[1], ccfg.rank), jnp.float32)

    step = partial(client_step, cfg, block=g_local.astype(jnp.float32),
                   w=None, n_i=None, lam0=lam, t0=0, sched=None,
                   faults=None, axes=axes, track=False)
    c, _ = jax.lax.scan(lambda c, t: (step(c, t), None),
                        client_init(cfg, u, v), jnp.arange(ccfg.rounds))
    v_mean = jax.lax.pmean(c["v"], axes)  # (k, r)
    return (c["u"] @ v_mean.T).astype(g_local.dtype)


def median_aggregate(g: Array, axes) -> Array:
    """Coordinate-wise median over the DP workers: the Byzantine-robust
    fallback for leaves too small to factorize (norm scales, biases).
    Costs one all-gather of a small tensor."""
    gathered = fz.gather_clients(g, axes)
    return jnp.median(gathered.astype(jnp.float32), axis=0).astype(g.dtype)


def aggregate_leaf(g: Array, axes, ccfg: CompressConfig, key: Array) -> Array:
    """Dispatch one gradient leaf: DCF-PCA on the trailing 2-D matrix of
    big >=2-D leaves (leading layer-stack / expert dims are vmapped via a
    single collapsed batch dim); coordinate-wise median for the rest."""
    if (g.ndim >= 2 and min(g.shape[-2:]) >= ccfg.min_dim
            and ccfg.rank < min(g.shape[-2:])):
        if g.ndim == 2:
            return consensus_compress(g, axes, ccfg, key)
        lead = int(np.prod(g.shape[:-2]))
        flat = g.reshape(lead, *g.shape[-2:])
        keys = jax.random.split(key, lead)
        out = jax.vmap(
            lambda gi, ki: consensus_compress(gi, axes, ccfg, ki)
        )(flat, keys)
        return out.reshape(g.shape)
    return median_aggregate(g, axes)


def aggregate_tree(grads, axes, ccfg: CompressConfig, key: Array):
    leaves, treedef = jax.tree.flatten(grads)
    keys = jax.random.split(key, len(leaves))
    out = [aggregate_leaf(g, axes, ccfg, k) for g, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def compression_ratio(shape: tuple[int, ...], ccfg: CompressConfig) -> float:
    """Static per-step comm bytes: compressed / all-reduce.

    Counts what actually crosses the wire per worker: per consensus
    round either the dense f32 U factor (``m r * 4`` bytes) or, with
    ``topk_frac`` set, the top-k payload at ``k * (4 + 4)`` bytes --
    4 for the f32 value AND 4 for the int32 flat index.  Forgetting the
    index bytes would overstate the top-k savings exactly 2x.  The final
    V pmean (``k r`` f32) ships either way; the all-reduce reference is
    the dense ``m k`` f32 gradient.
    """
    if len(shape) < 2 or min(shape[-2:]) < ccfg.min_dim \
            or ccfg.rank >= min(shape[-2:]):
        return 1.0
    m, k = shape[-2:]
    if ccfg.topk_frac is None:
        round_bytes = m * ccfg.rank * 4
    else:
        round_bytes = fz.topk_k(m * ccfg.rank, ccfg.topk_frac) * (4 + 4)
    compressed = ccfg.rounds * round_bytes + k * ccfg.rank * 4
    return compressed / (m * k * 4)
