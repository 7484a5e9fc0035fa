"""Shared machinery for consensus-factorization RPCA (paper Sec. 2.2).

Implements the *local* computation of Algorithm 1 -- everything a single
client does between two consensus rounds -- as pure functions reused by:

  * ``cf_pca``  (centralized, E=1),
  * ``dcf_pca`` simulated-client engine (vmap over the client axis),
  * ``dcf_pca`` sharded engine (shard_map over the device mesh),
  * ``distributed.grad_compress`` (robust gradient aggregation).

It also holds the one consensus dispatch those DCF rounds call over a
named client axis (``aggregate_sharded``: weighted mean, screens, robust
combine and the top-k wire).

Two inner solvers for Eq. (7) are provided:

``altmin``   Exact block-coordinate descent alternating the closed forms
             Eq. (15) (ridge solve for V given S, an r x r linear system)
             and Eq. (16) (soft-threshold for S given V).  Converges to the
             unique optimum of the jointly-convex subproblem; in practice
             2-4 sweeps suffice.  Never materializes S or the residual:
             the ridge RHS is rewritten as
                U^T (M - S) = (U^T U) V^T + U^T Psi
             so each sweep costs one fused ``huber_contract_v`` pass plus an
             r x r solve.

``huber_gd`` The paper's analysis path: gradient descent on the eliminated
             rho-strongly-convex Huber objective h(V) (Eq. 17), step size
             1/(rho + sigma_max(U)^2) per Lemma 1.

Both consume the fused kernels through ``repro.kernels.ops``.

Fused round (DESIGN.md Sec. 12): ``DCFConfig.fused`` selects how much of
the round rides the dual-contraction / epilogue-diagnostics kernel:

``"off"``   the PR-4 structure: J inner sweeps + a separate U-step
            contraction, diagnostics as a separate full-matrix pass.
``"diag"``  the default: identical factor math; the U-step pass also emits
            the Huber objective and ``||Psi||_F^2`` from its epilogue, so
            round diagnostics cost zero extra passes.
``"dual"``  the bandwidth-optimal opt-in: the final inner sweep is the
            dual-contraction kernel -- its ``Psi^T U`` output performs the
            last V update *exactly* as the unfused sweep would, its
            ``Psi V`` output feeds the U gradient, and the epilogue emits
            the diagnostics.  One streamed pass over M per local iteration
            is saved (J passes instead of J+1); the semantic change is
            that the U gradient is evaluated at the pre-final-sweep V.
            Usually the inner problem has essentially converged by then
            and recovery matches (tests/test_rpca_core), but on hard
            masked slow-anneal problems the stale gradient can settle
            into a worse stationary point for some inits -- hence opt-in,
            not default.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ops as core_ops
from repro.kernels import bitmask
from repro.kernels import ops as kops

Array = jax.Array

#: (objective data term, ||Psi||_F^2) measured in a fused pass's epilogue.
RoundDiag = tuple[Array, Array]

#: Name scopes of a solve's phases in the compiled programs (the round
#: itself is ``runtime.ROUND_SCOPE``): a profiler trace attributes each
#: device operation to the phase it was traced in.  Op metadata only.
CALIBRATE_SCOPE = "rpca.calibrate"  # robust_lam's medians
RIDGE_SCOPE = "rpca.ridge"  # the r x r Grams, factorizations and solves
CONSENSUS_SCOPE = "rpca.consensus"  # aggregation of the client factors


@dataclass(frozen=True)
class DCFConfig:
    """Hyperparameters of (D)CF-PCA.

    Defaults follow Sec. 4: decaying learning rate ``eta0 / (1 + t)``,
    ``K`` local iterations per consensus round.  ``lam``/``rho`` default to
    the convex-calibrated scaling ``rho * lambda_cvx`` with
    ``lambda_cvx = 1/sqrt(max(m, n))`` (see DESIGN.md Sec. 1); Theorem 2's
    necessary condition ``rho^2 <= lam^2 m n`` then always holds.
    """

    rank: int
    outer_iters: int = 50  # T, consensus rounds
    local_iters: int = 2  # K, local U-steps per round
    inner_sweeps: int = 3  # J, (V,S) solver sweeps per local U-step
    rho: float = 1e-2
    lam: float | None = None  # None => robust-scale heuristic (see robust_lam)
    # Threshold continuation (beyond-paper, EXPERIMENTS.md "perf/quality"):
    # lam_t = lam * max(lam_decay^t, lam_min_frac).  The paper's fixed-lam
    # scheme leaves a +-lam bias on every corrupted entry's Huber gradient
    # at stationarity (error floor ~ lam); annealing lam -- the exact analog
    # of IALM's growing-mu threshold continuation -- removes the floor.
    # Set lam_decay=1.0 for the paper-faithful fixed threshold.
    lam_decay: float = 1.0
    lam_min_frac: float = 1e-3
    eta0: float = 0.05
    lr_schedule: Literal["decay", "fixed", "theory"] = "decay"
    inner: Literal["altmin", "huber_gd"] = "altmin"
    # U-step conditioning.  "lipschitz" divides eta by the exact smoothness
    # of the U-subproblem (sigma_max(V)^2 + rho n_i/n) so Thm. 1's eta < 1/L
    # holds by construction; "newton" solves against the local Hessian
    # (V^T V + rho n_i/n I) -- an ALS-flavored beyond-paper accelerator;
    # "raw" is the literal Eq. (8) update.
    precondition: Literal["lipschitz", "newton", "raw"] = "lipschitz"
    impl: Literal["auto", "pallas", "ref"] = "auto"
    track_objective: bool = False  # record eliminated objective per round
    # Fused-round level (see module docstring): "diag" (default) keeps the
    # exact PR-4 factor math and gets the round diagnostics free from the
    # U-step pass's epilogue; "dual" additionally streams one fewer
    # full-matrix pass per local iteration by evaluating the U gradient at
    # the pre-final-sweep V -- choose it when HBM bandwidth dominates and
    # accept that the half-sweep-stale gradient can settle into a worse
    # stationary point on hard masked problems with unlucky inits (seen at
    # 128x128 r=5, 70% observed, slow anneal); "off" is the literal PR-4
    # structure (diagnostics as a separate pass).
    fused: Literal["off", "diag", "dual"] = "diag"
    # Compact data plane: store the observation mask bit-packed (uint8,
    # 8 cols/byte) in the problem pytree -- the kernels unpack per-tile in
    # VMEM, cutting steady-state mask traffic 32x.  Exact: unpack(pack(W))
    # round-trips any 0/1 mask bit-for-bit.
    pack_mask: bool = False
    # lam calibration subsample: cap the entries fed to robust_lam's
    # medians (None = exact over every entry, by selection: about 70
    # streaming passes of the plane).  ~64k (1 << 16) estimates the MAD
    # to well under a percent.
    lam_sample: int | None = None
    # Communication-optimal consensus wire (DESIGN.md Sec. 14).  With a
    # CompressConfig (its ``topk_frac`` must be set), each client ships
    # only the top-k entries of its weighted U delta per round, with an
    # error-feedback residual carried in the solver state so what the
    # top-k drops rides the next round's message.  ``None`` keeps the
    # dense factor wire bit-exact.
    consensus_compress: "CompressConfig | None" = None  # noqa: F821
    # Stale-consensus overlap: 1 applies each round's consensus delta one
    # round late (the all-reduce overlaps the next local sweep), guarded
    # by the fused epilogue's ||Psi||_F^2 scalar -- growth past
    # ``stale_guard``x the previous round's value trips a sticky fallback
    # to synchronous application.  0 = synchronous (default, bit-exact).
    consensus_delay: int = 0
    stale_guard: float = 4.0
    # Byzantine-robust consensus (DESIGN.md Sec. 17).  "weighted_mean" is
    # the PR-3 participation-weighted mean (default; bit-exact with the
    # pre-robustness engines).  "trimmed_mean" sorts every coordinate
    # across clients and drops ``floor(trim_frac * E)`` extremes per side
    # before averaging -- cheap, and optimal when corrupt payloads are
    # large-but-bounded outliers.  "coordinate_median" takes the
    # per-coordinate median -- tolerant to any corruption magnitude
    # (including NaN/inf payloads, which are masked out with one-vote-per-
    # client semantics) as long as honest clients hold a strict majority.
    # Robust aggregators are unweighted one-vote-per-client: a median of
    # column-count-weighted factors has no consistent meaning.
    aggregator: Literal[
        "weighted_mean", "trimmed_mean", "coordinate_median"
    ] = "weighted_mean"
    trim_frac: float = 0.25
    # Contribution-divergence screen: quarantine (drop from this round's
    # consensus) any client whose payload delta norm ``||U_i - U||_F``
    # exceeds ``divergence_screen`` times the cross-client median norm, or
    # is non-finite.  ``None`` disables the screen (bit-exact default).
    divergence_screen: float | None = None

    def resolved_lam(self, m: int, n: int) -> float:
        if self.lam is not None:
            return float(self.lam)
        # Fallback when no data is available to calibrate: the corruption
        # scale of the paper's generator.  Prefer `robust_lam(M)`.
        return 0.1 * float(jnp.sqrt(float(m) * float(n)))

    def lr(self, t: Array | int) -> Array:
        """Learning rate at consensus round t."""
        t = jnp.asarray(t, jnp.float32)
        if self.lr_schedule == "decay":
            return self.eta0 / (1.0 + t)  # paper Sec. 4.2
        if self.lr_schedule == "theory":  # Thm. 1: eta = c / sqrt(K T)
            return self.eta0 / jnp.sqrt(float(self.local_iters * self.outer_iters))
        return jnp.asarray(self.eta0, jnp.float32)

    def lam_at(self, lam0: Array | float, t: Array | int) -> Array:
        """Annealed threshold at round t (fixed when lam_decay == 1)."""
        if self.lam_decay >= 1.0:
            return jnp.asarray(lam0, jnp.float32)
        t = jnp.asarray(t, jnp.float32)
        frac = jnp.maximum(self.lam_decay**t, self.lam_min_frac)
        return jnp.asarray(lam0, jnp.float32) * frac

    def final_lam(self, lam0: Array | float) -> Array:
        return self.lam_at(lam0, self.outer_iters - 1)

    @classmethod
    def paper(cls, rank: int, **overrides) -> "DCFConfig":
        """Paper-faithful preset: fixed lam, decaying eta0=0.05, K=2
        (Sec. 4.2).  The 'lipschitz' conditioning only rescales eta to
        satisfy Thm. 1's eta < 1/L; pass precondition='raw' for the literal
        Eq. (8) update."""
        kw = dict(rank=rank, outer_iters=50, local_iters=2, inner_sweeps=3,
                  rho=1e-2, eta0=0.05, lr_schedule="decay", lam_decay=1.0,
                  precondition="lipschitz")
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def tuned(cls, rank: int, **overrides) -> "DCFConfig":
        """Beyond-paper preset (EXPERIMENTS.md 'quality hillclimb'):
        annealed threshold (IALM-style continuation), fixed eta with
        Lipschitz conditioning.  ~1e3x lower recovery error at the same
        iteration budget."""
        kw = dict(rank=rank, outer_iters=100, local_iters=2, inner_sweeps=3,
                  rho=1e-2, eta0=0.5, lr_schedule="fixed", lam_decay=0.9,
                  lam_min_frac=1e-3, precondition="lipschitz")
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def tuned_hard(cls, rank: int, **overrides) -> "DCFConfig":
        """Slow-anneal preset for hard corners of the (rank, sparsity)
        phase plane: a gentler threshold schedule tracks the slower decay
        of the clean residual at high rank (recovers r = 0.1 n exactly
        where the fast anneal plateaus; see benchmarks/fig2_phase.py)."""
        kw = dict(rank=rank, outer_iters=300, local_iters=2, inner_sweeps=3,
                  rho=1e-2, eta0=0.5, lr_schedule="fixed", lam_decay=0.97,
                  lam_min_frac=1e-3, precondition="lipschitz")
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def elastic(cls, rank: int, participation: float = 1.0,
                **overrides) -> "DCFConfig":
        """Preset for partial client participation (elastic topologies).

        With participation rate ``p`` each client's factors advance in only
        ~``p T`` of the ``T`` rounds while the threshold anneal ticks every
        round, so the fast anneal of :meth:`tuned` outruns the stragglers
        and freezes a biased threshold -- the *same* failure mode as
        masking (each round only updates a ``p`` fraction of the V blocks),
        so this delegates to :meth:`masked`'s slow anneal with the budget
        stretched by ``1/p`` (see benchmarks/elastic_bench.py for the
        phase curve).
        """
        return cls.masked(rank, observed_frac=participation, **overrides)

    @classmethod
    def masked(cls, rank: int, observed_frac: float = 0.7,
               **overrides) -> "DCFConfig":
        """Preset for partial observation (robust matrix completion).

        Under a mask the clean-entry residual decays roughly
        ``observed_frac`` times slower per round (each contraction only
        sees that fraction of the entries), so the fast anneal of
        :meth:`tuned` outruns the residual and freezes a biased threshold.
        Use the slow anneal and stretch the budget by ``1/observed_frac``
        (see benchmarks/masked_rpca_bench.py for the phase curve).
        """
        iters = int(round(300 / max(observed_frac, 0.3)))
        kw = dict(rank=rank, outer_iters=iters, local_iters=2,
                  inner_sweeps=3, rho=1e-2, eta0=0.5, lr_schedule="fixed",
                  lam_decay=0.97, lam_min_frac=1e-3,
                  precondition="lipschitz")
        kw.update(overrides)
        return cls(**kw)


def _masked_median(x: Array, keep: Array, count: Array) -> Array:
    """Median over ``keep``-flagged entries; interpolation arithmetic
    matches ``jnp.median`` bit-for-bit when every entry is kept."""
    xs = jnp.sort(jnp.where(keep, x, jnp.inf))
    return 0.5 * (xs[(count - 1) // 2] + xs[count // 2])


_I32_MIN = -(1 << 31)
_I32_MAX = (1 << 31) - 1
_NAN_KEY = 0x7FC00000  # key of the canonical (positive quiet) NaN


def _order_key(x: Array) -> Array:
    """int32 key of each f32 entry that orders as ``lax.sort`` orders the
    floats: zeros canonicalized as its comparator does (``x == 0``, so a
    flushed denormal ties too), every NaN above ``+inf``
    (``lax._canonicalize_float_for_sort``).  Negative floats' bit
    patterns are flipped onto ``[INT32_MIN + 1, -1]``."""
    x = jnp.where(x == 0, 0.0, x)
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(b < 0, _I32_MIN - b, b)
    return jnp.where(jnp.isnan(x), _NAN_KEY, key)


def _key_value(key: Array) -> Array:
    """The f32 whose :func:`_order_key` is ``key`` (zeros come back +0)."""
    b = jnp.where(key < 0, _I32_MIN - key, key)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _select_key(x: Array, k) -> Array:
    """Key of the ``k``-th smallest (0-based) entry of the f32 ``x``:
    bisection over the int32 key range, 32 fused count passes, no sort."""
    def step(_, bounds):
        lo, hi = bounds
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)  # floor mean, no overflow
        over = jnp.sum(_order_key(x) <= mid, dtype=jnp.int32) > k
        return jnp.where(over, lo, mid + 1), jnp.where(over, mid, hi)

    lo, _ = jax.lax.fori_loop(
        0, 32, step, (jnp.int32(_I32_MIN), jnp.int32(_I32_MAX)))
    return lo


def _select_median(x: Array, k_lo, k_hi) -> Array:
    """``0.5 * (x_(k_lo) + x_(k_hi))`` over the f32 ``x`` in sort order,
    with ``k_hi`` equal to ``k_lo`` or one above it.  The upper rank costs
    one pass: it shares ``k_lo``'s value when more than ``k_hi`` entries
    lie at or below it, else it is the least key above."""
    key_lo = _select_key(x, k_lo)
    key = _order_key(x)
    shared = jnp.sum(key <= key_lo, dtype=jnp.int32) > k_hi
    above = jnp.min(jnp.where(key > key_lo, key, _I32_MAX))
    key_hi = jnp.where(shared, key_lo, above)
    return 0.5 * (_key_value(key_lo) + _key_value(key_hi))


def _midpoint_ranks(n: int) -> tuple[int, int]:
    """The two ranks ``jnp.median`` averages over ``n`` entries: its
    ``quantile(method="midpoint")`` forms the index in f32, so
    ``q = 0.5 * (f32(n) - 1)`` rounds (at n = 2**26 it takes the single
    rank 2**25, not the integer midpoint of 2**25 - 1 and 2**25)."""
    q = np.float32(0.5) * (np.float32(n) - np.float32(1))
    return int(np.floor(q)), int(np.ceil(q))  # within jnp's [0, n - 1] clamp


def _exact_median(x: Array) -> Array:
    """``jnp.median`` of the f32 ``x`` by selection: the same bits, up to
    the sign of a zero median (which ``|x - med|`` does not see)."""
    med = _select_median(x, *_midpoint_ranks(x.size))
    return jnp.where(jnp.any(jnp.isnan(x)), jnp.nan, med)


@partial(jax.jit, static_argnames=("mult", "sample"))
@jax.named_scope(CALIBRATE_SCOPE)
def robust_lam(m_obs: Array, mult: float = 2.0,
               mask: Array | None = None,
               sample: int | None = None) -> Array:
    """Data-driven soft-threshold level: ``mult * 1.4826 * MAD(M)``.

    The shrinkage threshold must sit between the clean-entry residual scale
    (~entry std of L0) and the corruption magnitude; the median absolute
    deviation is immune to the sparse gross errors, so a small multiple of
    the robust std separates the two regimes.  Distributed setting: each
    shard computes its local MAD and the consensus uses their mean
    (medians commute with column partitioning only approximately; the
    threshold tolerates that slack).

    ``mask`` restricts both medians to the observed entries -- the hidden
    entries are stored as zeros and would otherwise drag the MAD toward 0.
    A bit-packed uint8 mask is accepted and unpacked.

    Both medians are exact order statistics found by selection (about 35
    streaming count passes each, no sort): bit-identical to ``jnp.median``
    unmasked and to :func:`_masked_median` masked, including their
    ``-0.0``, infinity and NaN behavior.  ``sample`` caps the number of
    entries fed to the medians (strided subsample): a ~64k-entry subsample
    estimates the MAD to well under a percent.  ``None`` keeps the exact
    MAD of every entry.

    Runs as one jitted program under :data:`CALIBRATE_SCOPE`, so eager
    callers (the sharded engine, the serving lanes) are named on the
    device too; inside a caller's jit it is inlined.
    """
    if mask is not None and bitmask.is_packed(mask):
        mask = bitmask.unpack_mask(mask, m_obs.shape[-1])
    n_cols = m_obs.shape[-1] if m_obs.ndim >= 2 else 1
    # Kept in its own shape: a ravel would copy the tiled plane.
    x = m_obs.astype(jnp.float32)
    keep = None if mask is None else mask > 0
    if sample is not None and x.size > sample:
        stride = -(-x.size // sample)
        # A stride sharing a factor with the column count would walk only
        # n/gcd(stride, n) distinct columns of the row-major ravel --
        # fatal under column-structured masks/corruption.  Bump it coprime
        # so the subsample sweeps every column.
        import math

        while n_cols > 1 and math.gcd(stride, n_cols) > 1:
            stride += 1
        x = x.ravel()[::stride]
        keep = None if keep is None else keep.ravel()[::stride]
    if keep is None:
        med = _exact_median(x)
        return mult * 1.4826 * _exact_median(jnp.abs(x - med))
    # _masked_median's ranks and +inf fill for the hidden entries.
    count = jnp.maximum(jnp.sum(keep.astype(jnp.int32)), 1)
    k_lo, k_hi = (count - 1) // 2, count // 2
    med = _select_median(jnp.where(keep, x, jnp.inf), k_lo, k_hi)
    dev = jnp.where(keep, jnp.abs(x - med), jnp.inf)
    return mult * 1.4826 * _select_median(dev, k_lo, k_hi)


# ---------------------------------------------------------------------------
# Consensus over a named client axis (DESIGN.md Secs. 14 and 17)
# ---------------------------------------------------------------------------
# Every DCF consensus boundary routes through ``aggregate_sharded``
# (machine-enforced by RPCA-R006), called once per client with the client
# axis named: under ``jax.vmap(axis_name=...)`` in the simulated engine,
# ``jax.shard_map`` over the data axes in the sharded one.  It owns the
# weighted-mean / trimmed-mean / coordinate-median dispatch, the
# contribution-divergence screen and the top-k wire, so a raw ``mean`` /
# ``pmean`` reintroduced in a round step would silently bypass Byzantine
# robustness.  Its ``weighted_mean``-no-screen fast path is the plain
# Eq. (9) ``pmean``, which under ``vmap`` is the stacked ``mean`` bit for
# bit.


def topk_k(d: int, frac: float) -> int:
    """Static kept-entry count for a ``d``-entry factor at ``frac``."""
    return max(1, min(d, int(round(frac * d))))


def topk_sparsify(g: Array, k: int) -> tuple[Array, Array]:
    """Top-``k``-by-magnitude entries of ``g`` as (values f32, flat int32
    indices) -- the wire payload of one compressed consensus message."""
    flat = g.astype(jnp.float32).ravel()
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = idx.astype(jnp.int32)
    return flat[idx], idx


def topk_reconstruct(vals: Array, idx: Array, size: int) -> Array:
    """Scatter-add a (values, indices) payload back to a dense flat
    vector.  Duplicate indices accumulate, so concatenated payloads from
    E clients reconstruct the *sum* of their sparse messages."""
    return jnp.zeros((size,), jnp.float32).at[idx.ravel()].add(vals.ravel())


def gather_clients(x: Array, axes) -> Array:
    """All-gather ``x`` over the (possibly tuple) client axes into one
    stacked ``(E, ...)`` axis -- identical on every client, so stacked
    post-processing (median, trim, screens) stays lock-step.  Under
    ``vmap`` the gather is the batched array itself (no copy)."""
    gathered = jax.lax.all_gather(x, axes)  # (E, ...) -- or nested per axis
    while gathered.ndim > x.ndim + 1:
        gathered = gathered.reshape(-1, *x.shape)
    return gathered


def robust_combine_stacked(
    x: Array,  # (E, ...) stacked per-client payloads
    active: Array | None,  # (E,) 0/1 participation (None = everyone)
    aggregator: str,
    trim_frac: float = 0.25,
) -> tuple[Array, Array]:
    """Byzantine-robust one-vote combination over a stacked client axis.

    The robust core behind ``DCFConfig.aggregator`` (DESIGN.md Sec. 17).
    A client with *any* non-finite entry is dropped entirely (one-vote
    semantics: a poisoned payload must not vote anywhere), inactive
    clients are masked to ``+inf`` so they sort past every live value,
    and the order statistics index a traced live count:

    ``coordinate_median``  ``0.5 * (xs[(c-1)//2] + xs[c//2])`` per
                           coordinate -- bit-exact with ``jnp.median``
                           when every client is live; tolerant to any
                           corruption magnitude while honest clients hold
                           a strict majority.
    ``trimmed_mean``       drops ``floor(trim_frac * E)`` extremes per
                           side (a static count) and averages the middle;
                           falls back to the median when fewer than one
                           live value would remain.

    Returns ``(agg, count)`` where ``count`` is the number of surviving
    clients; ``agg`` is zeros when no client survives (callers gate on
    ``count > 0`` and keep the previous consensus state).
    """
    e = x.shape[0]
    flat = x.reshape(e, -1).astype(jnp.float32)
    finite = jnp.all(jnp.isfinite(flat), axis=1)
    keep = finite if active is None else finite & (active > 0)
    cnt = jnp.sum(keep.astype(jnp.int32))
    xs = jnp.sort(jnp.where(keep[:, None], flat, jnp.inf), axis=0)
    c = jnp.maximum(cnt, 1)
    med = 0.5 * (xs[(c - 1) // 2] + xs[c // 2])
    if aggregator == "coordinate_median":
        agg = med
    elif aggregator == "trimmed_mean":
        k = int(trim_frac * e)
        pos = jnp.arange(e)[:, None]
        take = (pos >= k) & (pos < c - k)
        tsum = jnp.sum(jnp.where(take, xs, 0.0), axis=0)
        denom = c - 2 * k
        agg = jnp.where(denom >= 1, tsum / jnp.maximum(denom, 1), med)
    else:
        raise ValueError(f"unknown robust aggregator {aggregator!r}")
    agg = jnp.where(cnt > 0, agg, 0.0)
    return agg.reshape(x.shape[1:]), cnt


def screen_from_norms(nrm: Array, active: Array,
                      threshold: float) -> Array:
    """Contribution-divergence screen from per-client payload norms:
    quarantine (return 0) any client whose norm is non-finite or exceeds
    ``threshold`` times the median norm of the live cohort.

    The median baseline is computed over *active, finite* clients only --
    a quarantined client must not drag the baseline it is judged against.
    With every live norm at zero (a converged solve) nothing trips: the
    comparison floor keeps ``0 <= threshold * eps`` true.
    """
    ok = jnp.isfinite(nrm) & (active > 0)
    cnt = jnp.maximum(jnp.sum(ok.astype(jnp.int32)), 1)
    med = _masked_median(nrm, ok, cnt)
    keep = jnp.isfinite(nrm) & (nrm <= threshold * jnp.maximum(med, 1e-30))
    return keep.astype(jnp.float32)


@jax.named_scope(CONSENSUS_SCOPE)
def aggregate_sharded(
    cfg: DCFConfig,
    u_i: Array,
    u_prev: Array,
    *,
    axes,
    pt: Array,
    n_i: Array,
    uniform: bool,
    reduce_m=None,
    err: Array | None = None,
):
    """The consensus of one round, called once per client over the named
    client ``axes``; every client gets the same result.

    ``pt`` is this client's participation weight for the round (1.0 when
    no schedule), ``n_i`` its true column count (1.0 uniform base on equal
    blocks), ``uniform`` selects the bit-exact ``pmean`` fast path (no
    schedule, no ragged tail).  ``reduce_m`` psums row-partial scalars
    over the sharded engine's model axis so screen norms see full rows.
    All collectives run unconditionally on every client (lock-step
    invariant); the robust paths all-gather the stacked client payloads
    so every client computes the identical aggregate.

    Returns ``(u_new, wsum)``.  ``wsum`` is ``None`` on the unconditional
    fast path; otherwise it is the round's total consensus weight
    (weighted mean) or the number of surviving one-vote clients (robust
    aggregators), with ``wsum > 0`` meaning a consensus step happened.
    With ``err`` (the client's error-feedback residual) the round runs
    the top-k wire of ``cfg.consensus_compress`` and returns ``(u_new,
    wsum, err_new)``.
    """
    robust = cfg.aggregator != "weighted_mean"
    if reduce_m is None:
        reduce_m = _identity
    if err is not None:
        return _topk_wire(cfg, u_i, u_prev, axes, pt, n_i, uniform,
                          reduce_m, err)
    if not robust and cfg.divergence_screen is None:
        if uniform:
            return jax.lax.pmean(u_i, axes), None  # Eq. (9) consensus
        # Participation-weighted consensus (Eq. 9 generalized): one psum
        # of the pre-scaled factor -- same 2 E m r communication bound.
        u_g, wgt, wsum = _weighted(u_i, u_prev, pt, n_i, axes)
        u_cand = jax.lax.psum(wgt * u_g, axes)
        return jnp.where(wsum > 0, u_cand, u_prev), wsum
    one = jnp.ones((), jnp.float32)
    delta = (u_i - u_prev).astype(jnp.float32)
    stacked = gather_clients(delta, axes)  # (E, m_loc, r)
    active = gather_clients(pt * one, axes)  # (E,)
    e = stacked.shape[0]
    # One-vote finiteness is a *global* per-client property: psum the
    # non-finite counts over the model axis so every row shard agrees on
    # who is quarantined.
    bad = reduce_m(
        jnp.sum((~jnp.isfinite(stacked.reshape(e, -1))).astype(
            jnp.float32), axis=1)
    )
    active = active * (bad == 0).astype(jnp.float32)
    if cfg.divergence_screen is not None:
        sq = jnp.sum(stacked.reshape(e, -1) ** 2, axis=1)
        nrm = jnp.sqrt(reduce_m(sq))
        active = active * screen_from_norms(
            nrm, active, cfg.divergence_screen
        )
    if robust:
        # One vote per client: a median/trim of column-count-weighted
        # factors has no consistent meaning, so ragged weights are
        # deliberately ignored here.
        agg, cnt = robust_combine_stacked(
            stacked, active, cfg.aggregator, cfg.trim_frac
        )
        u_new = jnp.where(
            cnt > 0, u_prev + agg.astype(u_prev.dtype), u_prev
        )
        return u_new, cnt.astype(jnp.float32)
    # Screened weighted mean over the gathered stack (every client holds
    # the same stack, so no further collective is needed).
    n_all = gather_clients(n_i * one, axes)
    raw = active * n_all
    wsum = jnp.sum(raw)
    w = raw / jnp.maximum(wsum, 1e-30)
    step = jnp.sum(
        w[:, None, None] * jnp.where(active[:, None, None] > 0, stacked,
                                     0.0),
        axis=0,
    )
    u_new = jnp.where(wsum > 0, u_prev + step.astype(u_prev.dtype), u_prev)
    return u_new, wsum


def _weighted(u_i, u_prev, pt, n_i, axes):
    """This client's payload and weight in the round's weighted consensus
    ``U = sum_i p_i n_i U_i / sum_i p_i n_i``, and the round's total
    weight.  A dropped-out client is excluded (its payload is the previous
    U, its weight 0); its weight in later rounds is still the full
    ``p_i n_i``."""
    raw_w = pt * n_i
    wsum = jax.lax.psum(raw_w, axes)
    return (jnp.where(pt > 0, u_i, u_prev),
            raw_w / jnp.maximum(wsum, 1e-30), wsum)


def _topk_wire(cfg: DCFConfig, u_i, u_prev, axes, pt, n_i, uniform,
               reduce_m, err):
    """Top-k consensus wire with error feedback (DESIGN.md Sec. 14).

    The consensus runs in delta form -- the weights sum to 1, so
    ``sum_i w_i U_i == U + sum_i w_i (U_i - U)`` -- and each client ships
    only the top-k of its weighted delta plus its residual ``err`` as a
    compact (k f32 values, k int32 indices) payload.  One all-gather
    moves the E payloads and every client scatter-adds the same
    concatenated sequence (lock-step safe, like a real psum).  What the
    top-k dropped stays in the returned residual and rides the next
    round's message, so compression error never accumulates:

        shipped_t + err_t = contrib_t + err_{t-1}

    (exact when k == m r).  A dropped client ships zeros and keeps its
    residual.  Robust aggregators ship the *unweighted* delta and, on
    receive, combine the E reconstructed payloads one vote each (after
    the divergence screen on the shipped norms); a poisoned payload must
    not poison its own residual forever, so non-finite residuals reset.
    """
    robust = cfg.aggregator != "weighted_mean"
    wsum = None
    if robust:
        wgt = jnp.float32(1.0)
    elif uniform:
        wgt = jnp.float32(1.0 / jax.lax.axis_size(axes))
    else:
        u_i, wgt, wsum = _weighted(u_i, u_prev, pt, n_i, axes)
    g = (wgt * (u_i - u_prev)).astype(jnp.float32) + err
    k = topk_k(g.size, cfg.consensus_compress.topk_frac)
    vals, idx = topk_sparsify(g, k)
    err_new = g - topk_reconstruct(vals, idx, g.size).reshape(g.shape)
    vals = jnp.where(pt > 0, vals, jnp.zeros_like(vals))
    err_new = jnp.where(pt > 0, err_new, err)
    vals_g = gather_clients(vals, axes)  # (E, k)
    idx_g = gather_clients(idx, axes)
    if not robust:
        delta = topk_reconstruct(vals_g, idx_g, g.size).reshape(g.shape)
        return u_prev + delta.astype(u_prev.dtype), wsum, err_new
    err_new = jnp.where(jnp.isfinite(err_new), err_new, 0.0)
    act = gather_clients(pt, axes)
    if cfg.divergence_screen is not None:
        nrm = jnp.sqrt(reduce_m(jnp.sum(vals_g * vals_g, axis=1)))
        act = act * screen_from_norms(nrm, act, cfg.divergence_screen)
    recon = jax.vmap(lambda vv, ii: topk_reconstruct(vv, ii, g.size))(
        vals_g, idx_g)
    delta, cnt = robust_combine_stacked(
        recon.reshape((-1,) + g.shape), act, cfg.aggregator, cfg.trim_frac
    )
    return (u_prev + delta.astype(u_prev.dtype), cnt.astype(jnp.float32),
            err_new)


@dataclass(frozen=True)
class DCFState:
    """Consensus state: ``u`` is global, ``v`` is per-client (leading E axis
    in the simulated engine, mesh-sharded in the SPMD engine)."""

    u: Array  # (m, r)
    v: Array  # (n_i, r) local / (E, n_i, r) stacked / (n, r) global view
    step: Array  # scalar int32


def init_state(key: Array, m: int, n_local: int, rank: int,
               dtype=jnp.float32) -> DCFState:
    """Random init. U ~ N(0, 1/sqrt(r)) keeps ||U V^T|| at O(1) scale.

    The factors never drop below f32 -- a bf16 *data* plane (compact
    storage for M) still iterates f32 factors, exactly like the kernels'
    f32 accumulation.
    """
    dtype = jnp.result_type(dtype, jnp.float32)
    ku, kv = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(jnp.asarray(rank, dtype))
    u = jax.random.normal(ku, (m, rank), dtype) * scale
    v = jax.random.normal(kv, (n_local, rank), dtype) * scale
    return DCFState(u=u, v=v, step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Inner solvers for Eq. (7):  argmin_{V,S} given U
# ---------------------------------------------------------------------------
def _identity(x: Array) -> Array:
    return x


def inner_solve_altmin(
    u: Array, v: Array, m_blk: Array, rho: float, lam: Array | float,
    sweeps: int, impl: str, reduce_m=_identity, w: Array | None = None,
) -> Array:
    """Block-coordinate descent on the jointly-convex (V, S) subproblem.

    Per sweep: ``V^T <- (G + rho I)^{-1} (G V^T + U^T Psi)`` with
    ``G = U^T U`` -- the S elimination identity (DESIGN.md Sec. 2).

    ``reduce_m`` sums partial contractions over the row (m) dimension when U
    is row-sharded across the "model" mesh axis (psum of the r x r Gram and
    the (n_i, r) contraction; identity in the unsharded case).

    Under an observation mask ``w`` the same identity holds for the
    *imputed* data ``P_Omega(M) + P_Omega_perp(U V^T)`` (hidden entries
    filled with the current model -- the EM / SoftImpute majorization):
    ``U^T (M_fill - S) == G V^T + U^T Psi_W`` with
    ``Psi_W = W * clip(M - U V^T, +-lam)``, so masking only changes the
    fused contraction, not the sweep structure.

    The (r, r) system matrix ``A = G + rho I`` is constant across the
    sweeps (U is fixed), so its inverse is formed once outside the scan
    and each sweep is one (n_i, r) x (r, r) product (:func:`_altmin_ctx`).
    """
    g, update = _altmin_ctx(u, rho, reduce_m)

    def sweep(v, _):
        contr = reduce_m(
            kops.huber_contract_v(u, v, m_blk, lam, w=w, impl=impl)
        )
        return update(v, contr), None

    v, _ = jax.lax.scan(sweep, v, None, length=sweeps)
    return v


def _altmin_ctx(u: Array, rho: float, reduce_m=_identity):
    """Per-U altmin context: the Gram matrix and a one-sweep V update.

    ``A = G + rho I`` is inverted once per U -- one Cholesky factor
    ``A = L L^T`` and one triangular solve for ``L^{-1}``, then
    ``A^{-1} = L^{-T} L^{-1}`` -- and each sweep applies it as a product,
    ``V^T + A^{-1} (C^T - rho V^T)``, which equals
    ``A^{-1} (G V^T + C^T)`` with ``C = Psi^T U`` (the sweep's
    contraction) and needs no ``G V^T``.  No sweep factors or
    back-substitutes: on the TPU a triangular solve inverts the factor's
    r x r diagonal block in one custom call, whose cost hardly depends
    on the width of the right-hand side, so it runs once per U instead
    of once per sweep.  Shared by :func:`inner_solve_altmin`
    and the fused dual round so the Gram gemm / psum / inverse run once
    per local iteration."""
    with jax.named_scope(RIDGE_SCOPE):
        g = reduce_m(core_ops.matmul(u.T, u))  # (r, r)
        eye = jnp.eye(g.shape[0], dtype=g.dtype)
        chol = jax.lax.linalg.cholesky(g + rho * eye,
                                       symmetrize_input=False)
        chol_inv = jax.lax.linalg.triangular_solve(
            chol, eye, left_side=True, lower=True)
        a_inv = core_ops.matmul(chol_inv.T, chol_inv)

    @jax.named_scope(RIDGE_SCOPE)
    def update(v: Array, contr: Array) -> Array:
        return v + core_ops.matmul(contr - rho * v, a_inv)

    return g, update


def _gd_ctx(u: Array, rho: float, reduce_m=_identity):
    """Per-U Huber-GD context: one Lemma-1 step from the contraction."""
    g = reduce_m(core_ops.matmul(u.T, u))
    step = 1.0 / (rho + core_ops.spectral_norm_ub_gram(g))

    def update(v: Array, contr: Array) -> Array:
        return v - step * (rho * v - contr)

    return g, update


def inner_solve_huber_gd(
    u: Array, v: Array, m_blk: Array, rho: float, lam: Array | float,
    sweeps: int, impl: str, reduce_m=_identity, w: Array | None = None,
) -> Array:
    """GD on ``h(V) = rho/2 ||V||^2 + H_lam(P_Omega(M - U V^T))`` (Lemma 1
    step size; masking only shrinks the data-term Lipschitz constant, so
    the unmasked 1/(rho + sigma_max(U)^2) step stays valid)."""
    _, update = _gd_ctx(u, rho, reduce_m)

    def sweep(v, _):
        contr = reduce_m(
            kops.huber_contract_v(u, v, m_blk, lam, w=w, impl=impl)
        )
        return update(v, contr), None

    v, _ = jax.lax.scan(sweep, v, None, length=sweeps)
    return v


def _u_step(cfg: DCFConfig, u_i: Array, v_i: Array, psi_v: Array,
            n_frac: Array | float, eta: Array) -> Array:
    """One gradient step on the local U copy from the contraction Psi V.

    grad_U L_i = (U V^T + S - M) V + (n_i/n) rho U = -Psi V + (n_i/n) rho U
    (rows of grad_U stay local under row sharding -- no collective).
    """
    grad_u = -psi_v + n_frac * cfg.rho * u_i
    if cfg.precondition == "raw":
        upd = eta * grad_u
    else:
        # For fixed (V, S) the U-subproblem is quadratic with Hessian
        # H = V^T V + rho (n_i/n) I  (r x r, local -- no collective).
        with jax.named_scope(RIDGE_SCOPE):
            gram_v = core_ops.matmul(v_i.T, v_i)
            if cfg.precondition == "newton":
                h = gram_v + n_frac * cfg.rho * jnp.eye(
                    gram_v.shape[0], dtype=gram_v.dtype
                )
                upd = eta * jnp.linalg.solve(h, grad_u.T).T
            else:  # "lipschitz": eta / L, L = sigma_max(V)^2 + rho n_i/n
                lip = (core_ops.spectral_norm_ub_gram(gram_v)
                       + n_frac * cfg.rho)
                upd = (eta / lip) * grad_u
    return u_i - upd


def local_round(
    u_global: Array,
    v: Array,
    m_blk: Array,
    *,
    cfg: DCFConfig,
    lam: Array | float,
    n_frac: Array | float,
    eta: Array,
    reduce_m=_identity,
    w: Array | None = None,
) -> tuple[Array, Array, RoundDiag | None]:
    """One client's work in one consensus round: K local iterations of
    {inner (V,S) solve; one gradient step on the local U copy} (Alg. 1).

    ``n_frac = n_i / n`` weights the client's share of the rho/2 ||U||^2
    regularizer (paper Eq. 11).  Returns ``(U_i, V_i, diag)`` -- the
    factors to be averaged / kept local, plus the round diagnostics
    ``(H_lam(R_W), ||Psi_W||_F^2)`` measured for free in the final fused
    pass's epilogue (``None`` under ``cfg.fused == "off"``; engines then
    fall back to a separate :func:`local_objective` pass).  The epilogue
    objective is the data term at the point of the last fused pass: under
    ``"diag"`` that is (U_i pre-U-step, V_i final); under ``"dual"`` it is
    one inner sweep earlier still -- (U_i pre-U-step, V_i pre-final-sweep),
    the same point the stale U gradient uses.  Either is a consistent
    per-round surrogate of the post-consensus objective; see runtime.py's
    diagnostics contract.

    ``w`` is this client's slice of the observation mask (dense 0/1 or
    bit-packed uint8): every residual contraction then runs over observed
    entries only (Psi_W = W * clip, fused in the kernel epilogue).

    Under ``cfg.fused == "dual"`` each local iteration streams M once less:
    the final inner sweep runs the dual-contraction kernel, whose
    ``Psi^T U`` output applies the last V update exactly while its
    ``Psi V`` output feeds the U gradient (evaluated one inner sweep
    stale -- see the module docstring).
    """
    altmin = cfg.inner == "altmin"
    inner = inner_solve_altmin if altmin else inner_solve_huber_gd
    dual = cfg.fused == "dual"
    diag_only = cfg.fused == "diag"
    make_ctx = _altmin_ctx if altmin else _gd_ctx

    def one_local_iter(carry, _):
        u_i, v_i = carry
        if dual:
            # J-1 plain sweeps; the J-th sweep is the fused dual pass.
            # One inner-solver context (Gram gemm / psum / factorization)
            # serves all J sweeps -- U is fixed within the iteration.
            _, update = make_ctx(u_i, cfg.rho, reduce_m)

            def sweep(v, _):
                contr = reduce_m(kops.huber_contract_v(
                    u_i, v, m_blk, lam, w=w, impl=cfg.impl
                ))
                return update(v, contr), None

            v_i, _ = jax.lax.scan(sweep, v_i, None,
                                  length=cfg.inner_sweeps - 1)
            cv, psi_v, obj, psi2 = kops.huber_dual_contract(
                u_i, v_i, m_blk, lam, w=w, impl=cfg.impl
            )
            # Exact final sweep from the dual's Psi^T U output.
            v_i = update(v_i, reduce_m(cv))
            diag = (obj, psi2)
        else:
            v_i = inner(u_i, v_i, m_blk, cfg.rho, lam, cfg.inner_sweeps,
                        cfg.impl, reduce_m, w)
            if diag_only:
                psi_v, obj, psi2 = kops.huber_contract_u_diag(
                    u_i, v_i, m_blk, lam, w=w, impl=cfg.impl
                )
                diag = (obj, psi2)
            else:
                psi_v = kops.huber_contract_u(u_i, v_i, m_blk, lam, w=w,
                                              impl=cfg.impl)
                diag = (jnp.zeros((), jnp.float32),) * 2
        return (_u_step(cfg, u_i, v_i, psi_v, n_frac, eta), v_i), diag

    (u_i, v_i), diags = jax.lax.scan(
        one_local_iter, (u_global, v), None, length=cfg.local_iters
    )
    if cfg.fused == "off":
        return u_i, v_i, None
    return u_i, v_i, (diags[0][-1], diags[1][-1])


def finalize(u: Array, v: Array, m_blk: Array, lam: Array | float,
             impl: str, w: Array | None = None) -> tuple[Array, Array]:
    """Recovered ``(L_i, S_i)`` for output (Alg. 1 return).

    ``L = U V^T`` is dense (the completion estimate extends to hidden
    entries); ``S`` is supported on the observed entries only.
    """
    l_blk = core_ops.matmul(u, v.T)
    s_blk = kops.residual_shrink(u, v, m_blk, lam, w=w, impl=impl)
    return l_blk, s_blk


def local_objective(u: Array, v: Array, m_blk: Array, rho: float,
                    lam: Array | float, n_frac: Array | float,
                    w: Array | None = None) -> Array:
    """g_i(U) surrogate at the current (V): eliminated objective Eq. (17)
    plus this client's share of the U regularizer.  Masked: the Huber term
    sums over observed entries only (H_lam(0) == 0).  A bit-packed mask is
    unpacked; a bf16 data block is upcast (the residual is f32 either way).
    """
    if w is not None and bitmask.is_packed(w):
        w = bitmask.unpack_mask(w, m_blk.shape[-1])
    resid = m_blk.astype(jnp.float32) - core_ops.matmul(u, v.T)
    data = (
        core_ops.huber_loss(resid, lam)
        if w is None
        else core_ops.masked_huber_loss(resid, lam, w)
    )
    return data + 0.5 * rho * (jnp.sum(v * v) + n_frac * jnp.sum(u * u))


def reg_terms(u: Array, v: Array, rho: float,
              n_frac: Array | float) -> Array:
    """The rho/2 regularizer share added to an epilogue-measured data term
    to reconstruct g_i (cheap: factor norms only, no full-matrix pass)."""
    return 0.5 * rho * (jnp.sum(v * v) + n_frac * jnp.sum(u * u))
