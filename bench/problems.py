"""Synthetic low-rank-plus-sparse problems, made on the device from a seed.

The generator of the paper's Sec. 4.1 (after Candes, Li, Ma and Wright,
JACM 2011, Sec. 4.1), copied from ``repro.core.problems`` so that the
benchmark's inputs do not move with the program:

* ``L0 = U0 V0^T`` with standard-Gaussian factors;
* gross corruptions ``+-magnitude`` on a random support of density
  ``sparsity``.  The support is drawn entry by entry (the Bernoulli model of
  Candes et al.), not as exactly ``s m n`` positions without replacement:
  that would sort a permutation of every entry of the plane;
* an optional uniform observation mask of density ``observed``; the
  observed matrix and ``S0`` are zero where it is zero.

A problem is made column block by column block: block ``b`` of a matrix
shares ``U0`` with every other block and draws its own ``V0``, ``S0`` and
mask from keys folded with ``b``.  One chip makes block 0 at the full
width; a mesh makes one block per device, so no device ever holds the
whole problem.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def base_key(seed: int) -> jax.Array:
    """A raw PRNG key holding all 64 bits of ``seed``."""
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def host_key(seed: int, index: int) -> np.ndarray:
    """A raw key for the ``index``-th request of a run, made on the host
    (no device program, so nothing compiles when a new index appears)."""
    s = (int(seed) * 1_000_003 + int(index) + 1) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def block(key: jax.Array, b, *, m: int, nb: int, rank: int,
          sparsity: float, magnitude: float, observed: float = 1.0):
    """Column block ``b`` of a problem: ``(m_obs, mask, l0, s0)``.

    ``mask`` is ``None`` when ``observed >= 1``.  ``b`` may be traced.
    """
    k_u, k_v, k_s, k_sign, k_w = jax.random.split(key, 5)
    k_v, k_s, k_sign, k_w = (jax.random.fold_in(k, b)
                             for k in (k_v, k_s, k_sign, k_w))
    u0 = jax.random.normal(k_u, (m, rank), jnp.float32)
    v0 = jax.random.normal(k_v, (nb, rank), jnp.float32)
    l0 = jnp.dot(u0, v0.T, precision=HIGHEST)
    support = jax.random.bernoulli(k_s, sparsity, (m, nb))
    sign = jax.random.rademacher(k_sign, (m, nb), jnp.float32)
    s0 = jnp.where(support, sign * jnp.float32(magnitude), 0.0)
    if observed >= 1.0:
        return l0 + s0, None, l0, s0
    mask = (jax.random.uniform(k_w, (m, nb)) < observed).astype(jnp.float32)
    return mask * (l0 + s0), mask, l0, mask * s0


def make(seed: int, *, m: int, n: int, rank: int, sparsity: float,
         magnitude: float, observed: float = 1.0, mesh=None,
         axis: str = "data", truth: bool = False, parts: int = 1):
    """One problem on the device(s).

    Returns ``(m_obs, mask)``, or ``(l0, s0)`` with ``truth=True`` (the
    same bits: both come from :func:`block` with the same keys).  With a
    ``mesh`` the columns are split over ``axis``, one block per device,
    each made on its own device.  Without one, ``parts`` makes the same
    blocks on one device, side by side.
    """
    fn = _program(m, n, rank, float(sparsity), float(magnitude),
                  float(observed), mesh, axis, truth, parts)
    return fn(base_key(seed))


@functools.lru_cache(maxsize=None)
def _program(m, n, rank, sparsity, magnitude, observed, mesh, axis, truth,
             parts):
    kw = dict(rank=rank, sparsity=sparsity, magnitude=magnitude,
              observed=observed)

    def pick(out):
        m_obs, mask, l0, s0 = out
        return (l0, s0) if truth else (m_obs, mask)

    if mesh is None:
        def side_by_side(k):
            out = jax.vmap(lambda b: pick(block(k, b, m=m, nb=n // parts,
                                                **kw)))(jnp.arange(parts))
            return tuple(None if a is None else
                         jnp.concatenate(list(a), axis=1) for a in out)

        return jax.jit(side_by_side)
    from jax.sharding import PartitionSpec as P

    parts = mesh.shape[axis]

    def local(k):
        return pick(block(k, jax.lax.axis_index(axis), m=m, nb=n // parts,
                          **kw))

    col = P(None, axis)
    second = col if truth or observed < 1.0 else P()
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(),
                                 out_specs=(col, second), check_vma=False))
