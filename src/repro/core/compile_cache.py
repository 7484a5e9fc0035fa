"""Shape-bucketed AOT executable cache (DESIGN.md Sec. 13).

On the serving path the dominant cost at a *fresh tenant shape* is not the
solve but XLA trace + compile: every new ``(m, n, rank, method, dtype)``
combination pays seconds of compilation for milliseconds of math.  This
module removes that wall for the front door (``repro.rpca.solve(...,
compile_policy=...)``) and the serving lanes:

* **Buckets.**  ``m`` and ``n`` round *up* to a geometric bucket grid
  (``bucket_min * bucket_ratio^k``); ``rank``/``method``/``dtype``/run
  mode stay exact (they live in the cache key via the solver config and
  operand signature).  All shapes inside one bucket share one executable.

* **Padding rides the Omega plane.**  An admitted problem is zero-padded
  into its bucket *behind the observation mask* (mask-zero rows/columns)
  -- the PR-2/PR-3 plumbing already proves mask-zero padding is
  semantics-free for every solver here, so the padded tail never
  influences the solve and results are trimmed back to the true shape.
  Padding and trimming are **host-side numpy** ops: a device pad/slice
  would specialize on the true shape and re-introduce a compile per
  tenant shape.

* **AOT.**  Each bucket's solver program is lowered and compiled once
  (``jax.jit(prog, donate_argnums=...).lower(*args).compile()``); later
  dispatches at any same-bucket shape call the cached executable with
  zero retrace / zero XLA compilation (test-asserted).

* **LRU budget.**  Entries are evicted least-recently-used past
  ``CompilePolicy.max_entries`` / ``max_bytes`` (sized via the
  executable's ``memory_analysis``).  Eviction only drops the cache's
  reference -- executables already handed to a lane keep working.

The cache is method-agnostic: solvers opt in by registering an
``AOTHooks`` record (see ``repro.rpca``) whose ``program(cfg, run_cfg)``
returns a pure ``prog(m_obs, key, mask, warm, lam0) -> (l, s, u, v,
stats)`` traced once per bucket.  Specs the hooks cannot express
(batched, meshed, simulated-client, participation) silently fall back to
the regular jit dispatch -- recorded as a bypass, never an error.
"""
from __future__ import annotations

import math
from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import validate

Array = jax.Array


# ---------------------------------------------------------------------------
# Policy: bucket geometry + cache budget
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CompilePolicy:
    """Bucketing and budget knobs for the AOT executable cache.

    ``bucket_min``    smallest bucket edge; every dimension rounds up to
                      at least this (tiny problems share one executable).
    ``bucket_ratio``  geometric growth factor between bucket edges
                      (> 1); 2.0 means at most 4x padded area, ~1.5x
                      per-dimension padding in expectation.
    ``max_entries``   LRU entry budget for the cache this policy admits
                      into.
    ``max_bytes``     optional byte budget over the cached executables
                      (code + temp + output footprint from XLA's
                      ``memory_analysis``); ``None`` = unbounded.  The
                      most recent entry is always kept.
    """

    bucket_min: int = 64
    bucket_ratio: float = 2.0
    max_entries: int = 32
    max_bytes: int | None = None

    def __post_init__(self) -> None:
        validate.check_compile_policy(
            self.bucket_min, self.bucket_ratio, self.max_entries,
            self.max_bytes,
        )


#: The policy behind ``compile_policy="aot"`` and the serving lanes.
AOT = CompilePolicy()


def resolve_policy(
    policy: "CompilePolicy | str | None",
) -> CompilePolicy | None:
    """Normalize a ``compile_policy=`` argument.

    ``None`` / ``"off"`` -> no caching (regular jit dispatch), ``"aot"``
    -> the default :data:`AOT` policy, a :class:`CompilePolicy` passes
    through.
    """
    if policy is None:
        return None
    if isinstance(policy, CompilePolicy):
        return policy
    if isinstance(policy, str):
        if policy == "aot":
            return AOT
        if policy == "off":
            return None
    raise validate.unknown_compile_policy(policy)


def bucket_dim(x: int, policy: CompilePolicy) -> int:
    """Round one dimension up to the policy's geometric bucket grid."""
    if x < 1:
        raise ValueError(f"dimension must be >= 1 to bucket, got {x}")
    b = policy.bucket_min
    while b < x:
        # ceil keeps integer buckets; ratio > 1 guarantees progress.
        b = int(math.ceil(b * policy.bucket_ratio))
    return b


def bucket_shape(
    m: int, n: int, policy: CompilePolicy
) -> tuple[int, int]:
    """The ``(m, n)`` bucket an admission pads into."""
    return bucket_dim(m, policy), bucket_dim(n, policy)


# ---------------------------------------------------------------------------
# Stats + the LRU cache
# ---------------------------------------------------------------------------
@dataclass
class CacheStats:
    """Cumulative cache counters (monotonic over the cache's lifetime;
    ``clear()`` drops entries but keeps counting, so deltas across an
    operation are always meaningful)."""

    hits: int = 0
    misses: int = 0
    compiles: int = 0
    evictions: int = 0

    def snapshot(self) -> "CacheStats":
        return replace(self)

    def as_dict(self) -> dict[str, int]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "compiles": self.compiles,
            "evictions": self.evictions,
        }


def _executable_bytes(compiled: Any) -> int:
    """Resident-footprint estimate for one executable (code + temp +
    output buffers); 0 when the backend exposes no memory analysis."""
    try:
        ma = compiled.memory_analysis()
        return int(
            getattr(ma, "generated_code_size_in_bytes", 0)
            + getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
        )
    except Exception:  # noqa: BLE001 -- backend-dependent, best effort
        return 0


@dataclass
class _Entry:
    compiled: Any
    nbytes: int


class CompileCache:
    """LRU store of AOT-compiled executables keyed by (method, config,
    run mode, operand signature).  One instance (the module default) is
    shared by the front door and every service lane; tests build fresh
    instances for isolation."""

    def __init__(self) -> None:
        self._entries: "OrderedDict[Any, _Entry]" = OrderedDict()
        self.stats = CacheStats()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Any) -> bool:
        return key in self._entries

    def items(self) -> list[tuple[Any, Any]]:
        """``(key, compiled executable)`` pairs, least recently used
        first."""
        return [(k, e.compiled) for k, e in self._entries.items()]

    @property
    def nbytes(self) -> int:
        """Total estimated footprint of the cached executables."""
        return sum(e.nbytes for e in self._entries.values())

    def get(
        self, key: Any, build: Callable[[], Any], policy: CompilePolicy
    ) -> Any:
        """The cached executable for ``key``, building (and admitting
        under ``policy``'s budget) on a miss."""
        ent = self._entries.get(key)
        if ent is not None:
            self._entries.move_to_end(key)
            self.stats.hits += 1
            return ent.compiled
        self.stats.misses += 1
        compiled = build()
        self.stats.compiles += 1
        self._entries[key] = _Entry(compiled, _executable_bytes(compiled))
        self._evict(policy)
        return compiled

    def _evict(self, policy: CompilePolicy) -> None:
        while len(self._entries) > policy.max_entries or (
            policy.max_bytes is not None
            and self.nbytes > policy.max_bytes
            and len(self._entries) > 1  # the newest entry always stays
        ):
            self._entries.popitem(last=False)
            self.stats.evictions += 1

    def clear(self) -> None:
        """Drop every entry (cold behavior restored); counters persist."""
        self._entries.clear()


_DEFAULT_CACHE = CompileCache()


def default_cache() -> CompileCache:
    """The process-wide cache shared by ``solve`` and the service lanes."""
    return _DEFAULT_CACHE


# ---------------------------------------------------------------------------
# Cached front-door dispatch
# ---------------------------------------------------------------------------
def arg_signature(tree: Any) -> tuple:
    """Hashable (shape, dtype) signature of a pytree's array leaves --
    the operand part of a cache key (bucket shape, data dtype, key
    style and warm layout are all captured here)."""
    return tuple(
        (tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(tree)
    )


def _pad2(x: Any, mb: int, nb: int, dtype: Any = None) -> np.ndarray:
    """Host-side zero-pad of a 2-D array into ``(mb, nb)`` (always a
    fresh buffer, so donating the device copy never invalidates caller
    state)."""
    arr = np.asarray(x, dtype)
    out = np.zeros((mb, nb), arr.dtype)
    out[: arr.shape[0], : arr.shape[1]] = arr
    return out


def _trim2(x: Array | None, m: int, n: int) -> Array | None:
    if x is None or tuple(x.shape) == (m, n):
        return x
    # Host-side trim: a device slice would compile per true shape.
    return jnp.asarray(np.asarray(x)[:m, :n])


def _trim_rows(x: Array | None, m: int) -> Array | None:
    if x is None or x.shape[0] == m:
        return x
    return jnp.asarray(np.asarray(x)[:m])


def _admit(aot: Any, spec: Any, cfg: Any, m: int, n: int, mb: int,
           nb: int) -> tuple:
    """Build the padded operand tuple ``(m_obs, key, mask, warm, lam0)``.

    The mask plane is always present: an unmasked admission gets the
    all-ones plane (numerically the unmasked path) and the bucket tail is
    mask-zero either way, so the padding never influences the solve.
    ``lam0`` is the *true-shape* convex threshold ``1/sqrt(max(m, n))``
    shipped as an operand (solvers that calibrate on-device ignore it).
    """
    xp = _pad2(spec.m_obs, mb, nb)
    w = np.zeros((mb, nb), np.float32)
    if spec.mask is not None:
        w[:m, :n] = np.asarray(spec.mask, np.float32)
    else:
        w[:m, :n] = 1.0
    key = spec.key if spec.key is not None else jax.random.PRNGKey(0)
    warm = None
    if spec.warm is not None:
        true_shapes = aot.warm_shapes(cfg, m, n)
        pad_shapes = aot.warm_shapes(cfg, mb, nb)
        padded = []
        for wf, (name, shape, desc), (_, target, _) in zip(
            spec.warm, true_shapes, pad_shapes
        ):
            validate.check_factor(wf, shape, name, desc)
            arr = np.asarray(wf)
            out = np.zeros(target, arr.dtype)
            out[tuple(slice(0, d) for d in shape)] = arr
            padded.append(jnp.asarray(out))
        warm = tuple(padded)
    lam0 = jnp.asarray(1.0 / math.sqrt(max(m, n)), jnp.float32)
    return jnp.asarray(xp), key, jnp.asarray(w), warm, lam0


def solve_cached(
    entry: Any,
    spec: Any,
    cfg: Any,
    run_cfg: Any,
    policy: CompilePolicy,
    cache: CompileCache | None = None,
) -> tuple | None:
    """Dispatch one solve through the AOT cache.

    Returns ``(l, s, u, v, stats, CacheStats snapshot)`` with results
    trimmed to the spec's true shape, or ``None`` when this spec is out
    of the cache's scope (no AOT hooks for the method, batched/meshed/
    simulated-client/participation specs, or tracer inputs) -- the
    caller then takes the regular jit path.
    """
    aot = getattr(entry, "aot", None)
    if aot is None:
        return None
    if (
        spec.batched
        or spec.mesh is not None
        or spec.num_clients is not None
        or spec.participation is not None
    ):
        return None
    if isinstance(spec.m_obs, jax.core.Tracer):
        return None  # called under jit: host-side padding is impossible
    cache = cache if cache is not None else default_cache()
    cfg = aot.resolve_cfg(cfg, spec)
    m, n = spec.shape
    mb, nb = bucket_shape(m, n, policy)
    args = _admit(aot, spec, cfg, m, n, mb, nb)
    key = (entry.name, cfg, run_cfg, arg_signature(args))

    def build():
        prog = aot.program(cfg, run_cfg)
        # Donate the data + mask planes: _admit always materializes
        # fresh buffers for them, so XLA can reuse the (mb, nb) planes
        # in place without invalidating any caller-visible array.
        return jax.jit(prog, donate_argnums=(0, 2)).lower(*args).compile()

    compiled = cache.get(key, build, policy)
    l, s, u, v, stats = compiled(*args)
    return (
        _trim2(l, m, n),
        _trim2(s, m, n),
        _trim_rows(u, m),
        _trim_rows(v, n),
        stats,
        cache.stats.snapshot(),
    )
