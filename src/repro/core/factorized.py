"""Shared machinery for consensus-factorization RPCA (paper Sec. 2.2).

Implements the *local* computation of Algorithm 1 -- everything a single
client does between two consensus rounds -- as pure functions reused by:

  * ``cf_pca``  (centralized, E=1),
  * ``dcf_pca`` simulated-client engine (vmap over the client axis),
  * ``dcf_pca`` sharded engine (shard_map over the device mesh),
  * ``distributed.grad_compress`` (robust gradient aggregation).

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


def consensus_weights(n_cols: Array | None, part: Array | None,
                      num_clients: int) -> tuple[Array, Array]:
    """Normalized consensus weights ``w_i = p_i n_i / sum_j p_j n_j``.

    ``n_cols`` is the (E,) vector of true per-client column counts (``None``
    => equal blocks), ``part`` the round's 0/1 participation mask (``None``
    => everyone).  Returns ``(w, wsum)`` where ``wsum = sum_j p_j n_j`` --
    callers gate the consensus on ``wsum > 0`` (an all-dropout round keeps
    the previous U).  Normalizing *before* the weighted sum keeps the
    equal-blocks full-participation case bit-exact with ``mean`` whenever E
    is a power of two: ``w_i == fl(1/E)`` exactly and scaling by a power of
    two commutes with every rounding step of the reduction.
    """
    raw = jnp.ones((num_clients,), jnp.float32)
    if n_cols is not None:
        raw = raw * n_cols
    if part is not None:
        raw = raw * part
    wsum = jnp.sum(raw)
    return raw / jnp.maximum(wsum, 1e-30), wsum


# ---------------------------------------------------------------------------
# Consensus aggregator dispatch (DESIGN.md Sec. 17)
# ---------------------------------------------------------------------------
# Every consensus-boundary code path in the DCF engines routes through one
# of the two functions below (machine-enforced by RPCA-R006): they own the
# weighted-mean / trimmed-mean / coordinate-median dispatch plus the
# contribution-divergence screen, so a raw ``jnp.mean`` / ``lax.pmean``
# reintroduced in an engine step would silently bypass Byzantine
# robustness.  The ``weighted_mean``-no-screen fast paths reproduce the
# PR-3 consensus op-for-op (bit-exactness is test-pinned).


@jax.named_scope(CONSENSUS_SCOPE)
def aggregate_stacked(
    cfg: DCFConfig,
    u_i: Array,
    u_prev: Array,
    *,
    n_cols: Array | None = None,
    part: Array | None = None,
    num_clients: int,
) -> tuple[Array, Array | None]:
    """Consensus over a stacked ``(E, m, r)`` client axis (simulated engine).

    Returns ``(u_new, wsum)``.  ``wsum`` is ``None`` on the unconditional
    fast path (full participation, no screen, weighted mean) -- callers
    gate no-op-round handling on ``wsum is not None`` exactly as before;
    otherwise it is the round's total consensus weight (weighted mean) or
    the number of surviving one-vote clients (robust aggregators), with
    ``wsum > 0`` meaning a consensus step actually happened.
    """
    e = num_clients
    robust = cfg.aggregator != "weighted_mean"
    if not robust and cfg.divergence_screen is None:
        if part is None:
            if n_cols is None:
                # Eq. (9): FedAvg consensus (bit-exact legacy path).
                return jnp.mean(u_i, axis=0), None
            w, _ = consensus_weights(n_cols, None, e)
            return jnp.sum(w[:, None, None] * u_i, axis=0), None
        # Dropped-out clients are excluded from the round's consensus;
        # their weight in later rounds is still the full p_i n_i.
        w, wsum = consensus_weights(n_cols, part, e)
        u_g = jnp.where(part[:, None, None] > 0, u_i, u_prev)
        u = jnp.where(
            wsum > 0, jnp.sum(w[:, None, None] * u_g, axis=0), u_prev
        )
        return u, wsum
    from repro.distributed import grad_compress as gcomp

    active = jnp.ones((e,), jnp.float32) if part is None else part
    delta = (u_i - u_prev).astype(jnp.float32)
    if cfg.divergence_screen is not None:
        active = active * gcomp.divergence_screen_mask(
            delta, active, cfg.divergence_screen
        )
    if robust:
        # One vote per client: a median/trim of column-count-weighted
        # factors has no consistent meaning, so ragged ``n_cols`` weights
        # are deliberately ignored here.
        agg, cnt = gcomp.robust_combine_stacked(
            delta, active, cfg.aggregator, cfg.trim_frac
        )
        u = jnp.where(cnt > 0, u_prev + agg.astype(u_prev.dtype), u_prev)
        return u, cnt.astype(jnp.float32)
    # Screened weighted mean: recompute the PR-3 weights over the clients
    # that survived the screen.
    w, wsum = consensus_weights(n_cols, active, e)
    u_g = jnp.where(active[:, None, None] > 0, u_i, u_prev)
    u = jnp.where(
        wsum > 0, jnp.sum(w[:, None, None] * u_g, axis=0), u_prev
    )
    return u, wsum


@jax.named_scope(CONSENSUS_SCOPE)
def aggregate_sharded(
    cfg: DCFConfig,
    u_i: Array,
    u_prev: Array,
    *,
    axes: tuple[str, ...],
    pt: Array,
    n_i: Array,
    uniform: bool,
    reduce_m=None,
) -> tuple[Array, Array | None]:
    """Consensus across mesh shards (SPMD engine); called per shard.

    ``pt`` is this shard's participation weight for the round (1.0 when no
    schedule), ``n_i`` its true column count (1.0 uniform base when not
    ragged), ``uniform`` selects the bit-exact ``pmean`` fast path (no
    schedule, no ragged tail).  ``reduce_m`` psums row-partial scalars over
    the model axis so screen norms see full rows.  All collectives run
    unconditionally on every shard (lock-step invariant); the robust paths
    all-gather the stacked client payloads so every shard computes the
    identical aggregate.  Returns ``(u_new, wsum)`` with the same ``wsum``
    contract as :func:`aggregate_stacked`.
    """
    robust = cfg.aggregator != "weighted_mean"
    if reduce_m is None:
        reduce_m = _identity
    if not robust and cfg.divergence_screen is None:
        if uniform:
            return jax.lax.pmean(u_i, axes), None  # Eq. (9) consensus
        # Participation-weighted consensus (Eq. 9 generalized):
        # U = sum_i p_i n_i U_i / sum_i p_i n_i, one psum of the
        # pre-scaled factor -- same 2 E m r communication bound.
        u_g = jnp.where(pt > 0, u_i, u_prev)
        raw_w = pt * n_i
        wsum = jax.lax.psum(raw_w, axes)
        wgt = raw_w / jnp.maximum(wsum, 1e-30)
        u_cand = jax.lax.psum(wgt * u_g, axes)
        return jnp.where(wsum > 0, u_cand, u_prev), wsum
    from repro.distributed import grad_compress as gcomp

    one = jnp.ones((), jnp.float32)
    delta = (u_i - u_prev).astype(jnp.float32)
    stacked = gcomp.gather_clients(delta, axes)  # (E, m_loc, r)
    active = gcomp.gather_clients(pt * one, axes)  # (E,)
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
        active = active * gcomp.screen_from_norms(
            nrm, active, cfg.divergence_screen
        )
    if robust:
        agg, cnt = gcomp.robust_combine_stacked(
            stacked, active, cfg.aggregator, cfg.trim_frac
        )
        u_new = jnp.where(
            cnt > 0, u_prev + agg.astype(u_prev.dtype), u_prev
        )
        return u_new, cnt.astype(jnp.float32)
    # Screened weighted mean over the gathered stack (every shard holds
    # the same stack, so no further collective is needed).
    n_all = gcomp.gather_clients(n_i * one, axes)
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

    The (r, r) system matrix ``G + rho I`` is constant across the sweeps
    (U is fixed), so it is Cholesky-factored once outside the scan and
    each sweep back-substitutes (``cho_solve``) instead of re-factorizing.
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
    """Per-U altmin context: the Gram matrix and a one-sweep V update
    (ridge back-substitution against the once-factored ``G + rho I``).
    Shared by :func:`inner_solve_altmin` and the fused dual round so the
    Gram gemm / psum / Cholesky run once per local iteration."""
    with jax.named_scope(RIDGE_SCOPE):
        g = reduce_m(core_ops.matmul(u.T, u))  # (r, r)
        g_reg = g + rho * jnp.eye(g.shape[0], dtype=g.dtype)
        cho = jax.scipy.linalg.cho_factor(g_reg)

    @jax.named_scope(RIDGE_SCOPE)
    def update(v: Array, contr: Array) -> Array:
        return jax.scipy.linalg.cho_solve(
            cho, core_ops.matmul(g, v.T) + contr.T).T

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
