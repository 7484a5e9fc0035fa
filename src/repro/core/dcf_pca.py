"""DCF-PCA -- Algorithm 1: distributed RPCA via consensus factorization.

Two execution engines with identical math:

``dcf_pca``          Simulated clients on one device: the E column blocks
                     live on a leading axis and the per-client local round
                     is ``vmap``-ed; consensus (Eq. 9) is a mean over that
                     axis.  This reproduces the paper's single-device
                     simulation exactly and backs all paper experiments.

``dcf_pca_sharded``  SPMD engine: ``M`` is column-sharded over the mesh's
                     data axes (every shard is one "client") and optionally
                     row-sharded over the model axis.  The consensus average
                     is a single ``lax.pmean`` of the (m, r) factor per
                     round -- the paper's 2 E m r communication bound, run
                     as a bandwidth-optimal ICI all-reduce.  V_i and S_i
                     never leave their shard (the privacy property).

Both engines run on the unified solver runtime (DESIGN.md Sec. 4): pass
``run=`` for convergence-controlled or chunked execution and
``warm=(U, V)`` to seed the factors from a prior solve.  Client topology
is elastic (DESIGN.md Sec. 10): ``n % num_clients != 0`` zero-pads ragged
columns behind a mask plane and weights the consensus by true per-client
counts, and ``participation=`` (a (T, E) 0/1 schedule or Bernoulli rate)
runs partial-participation rounds -- dropped clients freeze their ``V_i``
and are excluded from that round's weighted average.  In the sharded
engine the convergence residual is computed on the *consensus* U (with a
model-axis psum of the norms when rows are sharded), so the
``lax.while_loop`` predicate is identical on every shard and the collec-
tives stay lock-step.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import rpca as _rpca
from repro.core import factorized as fz
from repro.core import problems as prob
from repro.core import runtime as rt
from repro.core import validate
from repro.distributed import faults as flt
from repro.kernels import bitmask

Array = jax.Array


class DCFResult(NamedTuple):
    l: Array  # recovered low-rank matrix, client-blocked (E, m, n_i) or (m, n)
    s: Array  # recovered sparse matrix, same layout
    u: Array  # consensus left factor (m, r)
    v: Array  # right factors (E, n_i, r) or (n, r)
    stats: rt.SolveStats

    @property
    def history(self) -> Array:
        """Back-compat view: per-round global objective (0 if not tracked)."""
        return self.stats.objective


class DCFProblem(NamedTuple):
    """Simulated-engine problem pytree: client blocks + initial factors.

    ``mask`` carries the client-blocked observation mask (robust matrix
    completion); ``None`` keeps the fully-observed path bit-for-bit
    unchanged.

    Elastic topology (ISSUE 3): ``n_cols`` is the (E,) vector of *true*
    per-client column counts -- ``None`` means equal blocks (``n % E == 0``,
    the legacy layout).  A ragged ``n`` is zero-padded into equal slots by
    ``split_columns`` and the padding columns are excluded through a
    mask-zero plane, so a ragged problem always carries ``mask``.
    ``participation`` is a ``(T_sched, E)`` 0/1 round schedule (``None`` =
    every client, every round); round ``t`` uses row ``t % T_sched``, so a
    warm-started resume (``t0 = outer_iters``) wraps around the schedule.
    """

    blocks: Array  # (E, m, n_i) column blocks, one per client
    u_init: Array  # (m, r) server broadcast
    v_init: Array  # (E, n_i, r) per-client factors
    lam0: Array  # () resolved base threshold
    t0: Array  # () int32 schedule offset (warm starts resume, not restart)
    mask: Array | None = None  # (E, m, n_i) blocked observation mask
    n_cols: Array | None = None  # (E,) true per-client column counts
    participation: Array | None = None  # (T_sched, E) 0/1 round schedule
    faults: Array | None = None  # (T_f, E) int32 fault-code table


class _Carry(NamedTuple):
    u: Array
    v: Array
    diag: rt.Diag


def _inject_round_faults(
    p: DCFProblem, t: Array, u_i: Array, u_prev: Array
) -> tuple[Array, Array | None, Array | None]:
    """Apply round ``t``'s fault codes at the consensus boundary
    (simulated engine).  Returns ``(u_i, pt, v_mask)``: the possibly
    corrupted payload stack, the effective participation vector (crash /
    flaky votes dropped; ``None`` when unconditional) and the V-advance
    mask (only a crash freezes local state; ``None`` when all advance).
    """
    pt = None
    if p.participation is not None:
        pt = p.participation[jnp.mod(t, p.participation.shape[0])]
    if p.faults is None:
        return u_i, pt, pt
    code = flt.round_codes(p.faults, t)
    u_i = flt.corrupt_payload(code, u_i, u_prev)
    live = flt.live_mask(code)
    adv = flt.v_advance_mask(code)
    if pt is None:
        return u_i, live, adv
    return u_i, pt * live, pt * adv


# ---------------------------------------------------------------------------
# Engine 1: simulated clients (paper Sec. 4.1 "Implementation")
# ---------------------------------------------------------------------------
def _sim_local_rounds(cfg: fz.DCFConfig, p: DCFProblem, u: Array, v: Array,
                      eta: Array, lam_t: Array):
    """Server broadcasts U; clients run K local iterations concurrently
    (vmapped over the client axis).  Returns ``(u_i, v_new, diag_i,
    n_frac)`` -- the per-client factor proposals, epilogue diagnostics
    (None when ``cfg.fused == "off"``) and regularizer shares."""
    e = p.blocks.shape[0]
    if p.n_cols is None:
        # Equal blocks: the compile-time 1/E constant keeps this path
        # bit-exact with the pre-elastic engine.
        n_frac = 1.0 / e
        local = partial(fz.local_round, cfg=cfg, lam=lam_t,
                        n_frac=n_frac)
        if p.mask is None:
            u_i, v_new, diag_i = jax.vmap(
                lambda vb, mb: local(u, vb, mb, eta=eta)
            )(v, p.blocks)
        else:
            u_i, v_new, diag_i = jax.vmap(
                lambda vb, mb, wb: local(u, vb, mb, eta=eta, w=wb)
            )(v, p.blocks, p.mask)
    else:
        # Ragged blocks always carry a mask (padding columns are
        # mask-zero) and a per-client regularizer share n_i/n.
        n_frac = p.n_cols / jnp.sum(p.n_cols)
        local = partial(fz.local_round, cfg=cfg, lam=lam_t)
        u_i, v_new, diag_i = jax.vmap(
            lambda vb, mb, wb, nf: local(u, vb, mb, eta=eta, w=wb,
                                         n_frac=nf)
        )(v, p.blocks, p.mask, n_frac)
    return u_i, v_new, diag_i, n_frac


def _sim_objective(cfg: fz.DCFConfig, p: DCFProblem, u: Array, v: Array,
                   lam_t: Array, n_frac) -> Array:
    """Legacy (non-epilogue) global objective at the post-consensus state."""
    if p.n_cols is None:
        if p.mask is None:
            return jax.vmap(
                lambda vb, mb: fz.local_objective(
                    u, vb, mb, cfg.rho, lam_t, n_frac
                )
            )(v, p.blocks).sum()
        return jax.vmap(
            lambda vb, mb, wb: fz.local_objective(
                u, vb, mb, cfg.rho, lam_t, n_frac, w=wb
            )
        )(v, p.blocks, p.mask).sum()
    return jax.vmap(
        lambda vb, mb, wb, nf: fz.local_objective(
            u, vb, mb, cfg.rho, lam_t, nf, w=wb
        )
    )(v, p.blocks, p.mask, n_frac).sum()


def _sim_finalize(cfg: fz.DCFConfig, p: DCFProblem, u: Array, v: Array):
    if p.mask is None:
        l_blocks, s_blocks = jax.vmap(
            lambda vb, mb: fz.finalize(
                u, vb, mb, cfg.final_lam(p.lam0), cfg.impl
            )
        )(v, p.blocks)
    else:
        l_blocks, s_blocks = jax.vmap(
            lambda vb, mb, wb: fz.finalize(
                u, vb, mb, cfg.final_lam(p.lam0), cfg.impl, w=wb
            )
        )(v, p.blocks, p.mask)
    return (
        prob.merge_columns(l_blocks),
        prob.merge_columns(s_blocks),
        u,
        v,
    )


def make_solver(cfg: fz.DCFConfig, *, with_objective: bool = False) -> rt.Solver:
    """Runtime Solver for the simulated-client engine."""
    track = cfg.track_objective or with_objective
    if cfg.consensus_compress is not None or cfg.consensus_delay:
        return _make_wire_solver(cfg, track)

    def init(p: DCFProblem) -> _Carry:
        inf = jnp.asarray(jnp.inf, jnp.float32)
        return _Carry(u=p.u_init, v=p.v_init, diag=rt.Diag(inf, inf))

    def step(p: DCFProblem, c: _Carry, t: Array) -> _Carry:
        e = p.blocks.shape[0]
        t = t + p.t0
        eta = cfg.lr(t)
        lam_t = cfg.lam_at(p.lam0, t)
        # Fused epilogue diagnostics replace the separate objective pass
        # whenever the fused round measures them; participation/fault
        # rounds keep the legacy pass (a dropped client's epilogue
        # measures a local run whose factors are then discarded -- the
        # frozen state's objective is the meaningful one).
        fused_obj = (track and cfg.fused != "off"
                     and p.participation is None and p.faults is None)
        u_i, v_new, diag_i, n_frac = _sim_local_rounds(
            cfg, p, c.u, c.v, eta, lam_t
        )
        # Consensus boundary: inject the round's faults, then route the
        # aggregation through the dispatch (RPCA-R006) -- dropped-out /
        # crashed clients freeze their V_i (no decay toward zero) and are
        # excluded from the round's consensus; their weight in later
        # rounds is still the full p_i n_i.
        u_i, pt, v_mask = _inject_round_faults(p, t, u_i, c.u)
        v = (v_new if v_mask is None
             else jnp.where(v_mask[:, None, None] > 0, v_new, c.v))
        u, wsum = fz.aggregate_stacked(
            cfg, u_i, c.u, n_cols=p.n_cols, part=pt, num_clients=e
        )
        if fused_obj:
            # Free data terms from the kernel epilogues; only the factor-
            # norm regularizer is added (sum_i n_frac_i == 1, so the
            # stacked V and the consensus U take full weight).
            obj = diag_i[0].sum() + fz.reg_terms(u, v, cfg.rho, 1.0)
        elif track:
            obj = _sim_objective(cfg, p, u, v, lam_t, n_frac)
        else:
            obj = jnp.zeros((), jnp.float32)
        resid = jnp.linalg.norm(u - c.u) / (jnp.linalg.norm(c.u) + 1e-30)
        if wsum is not None:
            # A user-supplied schedule may contain an all-dropout row
            # (generated ones never do).  Such a round is a no-op: re-emit
            # the previous residual -- a zero here would read as
            # convergence to the rel_residual criterion -- and emit an
            # *inf* objective ("not measured": the frozen state would
            # trivially plateau), which suppresses the obj_plateau check
            # for this round and the next.
            resid = jnp.where(wsum > 0, resid, c.diag.residual)
            if track:
                obj = jnp.where(wsum > 0, obj, jnp.inf)
        return _Carry(u=u, v=v, diag=rt.Diag(obj, resid))

    def diagnostics(p: DCFProblem, c: _Carry) -> rt.Diag:
        return c.diag

    def finalize(p: DCFProblem, c: _Carry):
        return _sim_finalize(cfg, p, c.u, c.v)

    return rt.Solver(init, step, diagnostics, finalize)


def _make_wire_solver(cfg: fz.DCFConfig, track: bool) -> rt.Solver:
    """Simulated-client solver with the consensus *wire* features
    (DESIGN.md Sec. 14): top-k compressed deltas with error feedback
    (``cfg.consensus_compress``) and/or one-round stale application
    (``cfg.consensus_delay``).

    The consensus is reformulated in delta form -- the active-set weights
    sum to 1, so ``sum_i w_i U_i == U + sum_i w_i (U_i - U)`` -- and the
    per-client weighted deltas are what crosses the wire.  With
    compression each client ships only the top-k of its delta plus its
    error-feedback residual; the dropped remainder stays in the carry and
    rides the next round's message, so compression error never
    accumulates (exact when k == m r).  With ``consensus_delay=1`` the
    round's delta is parked in ``pending`` and applied at the *next*
    round (overlapping the all-reduce with the next local sweep in the
    SPMD engine); the fused epilogue's ||Psi||_F^2 scalar guards the
    staleness -- growth past ``cfg.stale_guard``x trips a sticky fallback
    to synchronous application.

    The carry is a dict so the extra state rides the runtime's generic
    pytree plumbing (batch freeze masks via ``tree_where`` included).
    """
    from repro.distributed import grad_compress as gcomp
    from repro.distributed import multihost as mh

    compress = cfg.consensus_compress
    delay = cfg.consensus_delay

    def init(p: DCFProblem) -> dict:
        inf = jnp.asarray(jnp.inf, jnp.float32)
        c = {"u": p.u_init, "v": p.v_init, "diag": rt.Diag(inf, inf)}
        if compress is not None:
            c["err"] = jnp.zeros((p.v_init.shape[0],) + p.u_init.shape,
                                 jnp.float32)
        if delay:
            c["pending"] = jnp.zeros(p.u_init.shape, jnp.float32)
            c["sync"] = jnp.zeros((), jnp.bool_)
            c["guard"] = inf
        return c

    robust = cfg.aggregator != "weighted_mean"
    screen = cfg.divergence_screen

    def step(p: DCFProblem, c: dict, t: Array) -> dict:
        e = p.blocks.shape[0]
        tg = t + p.t0
        eta = cfg.lr(tg)
        lam_t = cfg.lam_at(p.lam0, tg)
        fused_obj = (track and cfg.fused != "off"
                     and p.participation is None and p.faults is None)
        u_used = c["u"]
        u_i, v_new, diag_i, n_frac = _sim_local_rounds(
            cfg, p, u_used, c["v"], eta, lam_t
        )
        u_i, pt, v_mask = _inject_round_faults(p, tg, u_i, u_used)
        v = (v_new if v_mask is None
             else jnp.where(v_mask[:, None, None] > 0, v_new, c["v"]))
        with jax.named_scope(fz.CONSENSUS_SCOPE):
            wsum = None
            if robust:
                # One vote per client: unweighted deltas cross the wire; the
                # robust combine happens on the receive side.
                w = jnp.ones((e,), jnp.float32)
            elif pt is None:
                if p.n_cols is None:
                    w = jnp.full((e,), 1.0 / e, jnp.float32)
                else:
                    w, _ = fz.consensus_weights(p.n_cols, None, e)
            else:
                w, wsum = fz.consensus_weights(p.n_cols, pt, e)
                u_i = jnp.where(pt[:, None, None] > 0, u_i, u_used)
            # What crosses the wire: each client's weighted delta (their sum
            # is the consensus step; a dropped client's w is 0, an all-dropout
            # round sums to an exact no-op).  Robust aggregators ship the
            # *unweighted* delta and combine one-vote on receive.
            contrib = (w[:, None, None] * (u_i - u_used)).astype(jnp.float32)
            out = dict(c)
            if compress is None:
                if robust or screen is not None:
                    act = jnp.ones((e,), jnp.float32) if pt is None else pt
                    if screen is not None:
                        act = act * gcomp.divergence_screen_mask(
                            contrib, act, screen
                        )
                    if robust:
                        delta, cnt = gcomp.robust_combine_stacked(
                            contrib, act, cfg.aggregator, cfg.trim_frac
                        )
                        wsum = cnt.astype(jnp.float32)
                    else:
                        # Screened weighted mean: recompute the weights over
                        # the survivors (contrib already carries the original
                        # w, so rescale by the survivor renormalization).
                        w2, wsum = fz.consensus_weights(p.n_cols, act, e)
                        deltas = (u_i - u_used).astype(jnp.float32)
                        delta = jnp.sum(
                            w2[:, None, None]
                            * jnp.where(act[:, None, None] > 0, deltas, 0.0),
                            axis=0,
                        )
                        delta = jnp.where(wsum > 0, delta, 0.0)
                else:
                    delta = contrib.sum(axis=0)
            else:
                k = mh.topk_k(u_used.size, compress.topk_frac)
                flat = (contrib + c["err"]).reshape(e, -1)
                vals, idx = jax.vmap(lambda x: gcomp.topk_sparsify(x, k))(flat)
                recon = jax.vmap(
                    lambda vv, ii: gcomp.topk_reconstruct(vv, ii,
                                                          flat.shape[1])
                )(vals, idx)
                err_new = (flat - recon).reshape(c["err"].shape)
                if pt is not None:
                    # Dropped clients ship nothing and keep their residual.
                    vals = jnp.where(pt[:, None] > 0, vals, 0.0)
                    err_new = jnp.where(pt[:, None, None] > 0, err_new,
                                        c["err"])
                if robust:
                    # A poisoned payload must not poison its own error-
                    # feedback carry forever: non-finite residuals reset.
                    err_new = jnp.where(jnp.isfinite(err_new), err_new, 0.0)
                    act = jnp.ones((e,), jnp.float32) if pt is None else pt
                    if screen is not None:
                        # Judged on the *shipped* payload norms.
                        nrm = jnp.sqrt(jnp.sum(vals * vals, axis=1))
                        act = act * gcomp.screen_from_norms(nrm, act, screen)
                    delta, cnt = gcomp.robust_combine_stacked(
                        recon.reshape((e,) + u_used.shape), act,
                        cfg.aggregator, cfg.trim_frac,
                    )
                    wsum = cnt.astype(jnp.float32)
                else:
                    delta = gcomp.topk_reconstruct(
                        vals, idx, flat.shape[1]).reshape(u_used.shape)
                out["err"] = err_new
            if delay == 0:
                u = u_used + delta
            else:
                # Guard scalar: the fused epilogue's ||Psi||_F^2 (free since
                # the PR-5 kernels) or, with fused="off", the consensus-step
                # energy.  Divergence under staleness shows up as growth in
                # either; the trip is sticky -- once synchronous, stays
                # synchronous.
                if diag_i is not None:
                    scalar = diag_i[1].sum()
                else:
                    scalar = jnp.sum(delta * delta)
                # Trip on guard-factor growth OR a non-finite scalar (a hard
                # blowup must not slip through: NaN compares False with
                # everything, so the growth test alone would never fire).
                trip = jnp.logical_or(
                    ~jnp.isfinite(scalar),
                    jnp.isfinite(c["guard"])
                    & (scalar > cfg.stale_guard * c["guard"]),
                )
                sync = jnp.logical_or(c["sync"], trip)
                u = u_used + c["pending"] + jnp.where(sync, delta,
                                                      jnp.zeros_like(delta))
                out["pending"] = jnp.where(sync, jnp.zeros_like(delta), delta)
                out["sync"] = sync
                out["guard"] = scalar
        if fused_obj:
            obj = diag_i[0].sum() + fz.reg_terms(u, v, cfg.rho, 1.0)
        elif track:
            obj = _sim_objective(cfg, p, u, v, lam_t, n_frac)
        else:
            obj = jnp.zeros((), jnp.float32)
        resid = jnp.linalg.norm(u - u_used) / (
            jnp.linalg.norm(u_used) + 1e-30)
        if delay:
            # Round 0 applies nothing (its delta is pending): a zero
            # residual would read as instant convergence, so re-emit the
            # previous (inf at init).
            resid = jnp.where(t > 0, resid, c["diag"].residual)
        if wsum is not None:
            resid = jnp.where(wsum > 0, resid, c["diag"].residual)
            if track:
                obj = jnp.where(wsum > 0, obj, jnp.inf)
        out["u"] = u
        out["v"] = v
        out["diag"] = rt.Diag(obj, resid)
        return out

    def finalize(p: DCFProblem, c: dict):
        # Flush the in-flight delta: the stale pipeline must not drop the
        # last round's consensus step.
        u = c["u"] + c["pending"] if delay else c["u"]
        return _sim_finalize(cfg, p, u, c["v"])

    return rt.Solver(init, step, lambda p, c: c["diag"], finalize)


def _resolve_participation(
    participation: Array | float | None,
    rounds: int,
    num_clients: int,
    key: Array,
) -> Array | None:
    """Normalize the ``participation=`` argument into a (T, E) 0/1 schedule.

    A scalar is a Bernoulli rate: a ``(cfg.outer_iters, E)`` schedule is
    drawn from a key derived from the solve key (every round keeps at least
    one participant -- see ``problems.participation_schedule``).  A 2-D
    array is used as-is (static schedules; values outside {0, 1} are
    treated as participation weights p_i).
    """
    if participation is None:
        return None
    part = jnp.asarray(participation)
    if part.ndim == 0:
        return prob.participation_schedule(
            jax.random.fold_in(key, 0x9A7), rounds, num_clients, part
        )
    if part.ndim != 2 or part.shape[1] != num_clients:
        raise ValueError(
            f"participation schedule has shape {part.shape}, expected "
            f"(rounds, num_clients={num_clients})"
        )
    return part.astype(jnp.float32)


def make_problem(
    m_obs: Array,
    cfg: fz.DCFConfig,
    num_clients: int,
    key: Array,
    warm: tuple[Array, Array] | None = None,
    t0: int | Array | None = None,
    mask: Array | None = None,
    participation: Array | float | None = None,
    faults: "flt.FaultPlan | Array | None" = None,
) -> DCFProblem:
    """Assemble the simulated-engine problem pytree.  See
    ``cf_pca.make_problem`` for the warm-start ``t0`` schedule-resume
    convention.  ``mask`` is the (m, n) observation mask; it is split into
    the same column blocks as ``m_obs`` (each client sees its own slice of
    Omega) and the hidden entries of ``m_obs`` are zero-filled up front.

    Ragged ``n % num_clients != 0`` works: columns are zero-padded into
    equal slots and excluded via a mask-zero plane, and the per-client true
    counts ride along in ``n_cols`` (consensus weights).  ``participation``
    is a (T, E) 0/1 schedule or a Bernoulli rate (see
    :func:`_resolve_participation`).  ``faults`` is a deterministic
    :class:`repro.distributed.faults.FaultPlan` (or its (T_f, E) code
    table) injected at the consensus boundary."""
    validate.check_consensus_cfg(cfg, participation)
    validate.check_fault_plan(cfg, faults, num_clients)
    fault_tab = flt.resolve_faults(faults)
    if mask is not None:
        validate.check_mask(mask, m_obs.shape)
        m_obs = (mask * m_obs.astype(jnp.float32)).astype(m_obs.dtype)
    m, n = m_obs.shape
    # lam calibrates on the unpadded data -- padding columns are not
    # observations and must not drag the MAD toward zero.
    lam0 = (
        jnp.asarray(cfg.lam, jnp.float32)
        if cfg.lam is not None
        else fz.robust_lam(m_obs, mask=mask, sample=cfg.lam_sample)
    )
    blocks = prob.split_columns(m_obs, num_clients)  # (E, m, n_i), padded
    n_i = blocks.shape[-1]
    if n % num_clients:
        # Ragged: exclude the zero-padded tail columns via the Omega
        # plumbing (an all-ones base mask when the problem is unmasked).
        base = mask if mask is not None else jnp.ones(m_obs.shape,
                                                     jnp.float32)
        mask_blocks = prob.split_columns(base, num_clients)
        n_cols = jnp.asarray(
            prob.client_column_counts(n, num_clients), jnp.float32
        )
    else:
        mask_blocks = (
            None if mask is None else prob.split_columns(mask, num_clients)
        )
        n_cols = None
    if mask_blocks is not None and cfg.pack_mask:
        # Compact data plane: per-client mask slices stored bit-packed
        # (8 cols/byte); the kernels unpack per-tile in VMEM.
        mask_blocks = bitmask.pack_mask(mask_blocks)
    sched = _resolve_participation(
        participation, cfg.outer_iters, num_clients, key
    )
    if warm is None:
        k_u, k_v = jax.random.split(key)
        u0 = fz.init_state(k_u, m, n_i, cfg.rank, m_obs.dtype).u
        # Independent V_i inits per client ("randomly initializes V_i").
        v0 = jax.vmap(
            lambda k: fz.init_state(k, 1, n_i, cfg.rank, m_obs.dtype).v
        )(jax.random.split(k_v, num_clients))
    else:
        # Validate the full factor shapes eagerly: a warm (U, V) from a
        # solve with a different num_clients or n used to pass the old
        # rank-only check and fail (or silently broadcast) deep inside the
        # vmapped local round.
        u0, v0 = validate.check_warm_shapes(
            warm, ("U", "V"),
            ((m, cfg.rank), (num_clients, n_i, cfg.rank)),
            ("(m, rank)", "(E, n_i, rank)"),
            suffixes=("", f" for num_clients={num_clients}, n={n}"),
        )
    if t0 is None:
        t0 = 0 if warm is None else cfg.outer_iters
    return DCFProblem(
        blocks=blocks, u_init=u0, v_init=v0, lam0=lam0,
        t0=jnp.asarray(t0, jnp.int32), mask=mask_blocks,
        n_cols=n_cols, participation=sched, faults=fault_tab,
    )


@partial(jax.jit, static_argnames=("cfg", "num_clients", "run"))
def _solve(
    m_obs: Array,
    cfg: fz.DCFConfig,
    num_clients: int,
    key: Array,
    *,
    run: rt.RunConfig,
    warm: tuple[Array, Array] | None = None,
    mask: Array | None = None,
    participation: Array | float | None = None,
    faults: Array | None = None,
) -> DCFResult:
    solver = make_solver(cfg, with_objective=run.needs_objective)
    problem = make_problem(m_obs, cfg, num_clients, key, warm, mask=mask,
                           participation=participation, faults=faults)
    carry, stats = rt.run(solver, problem, cfg.outer_iters, run)
    l, s, u, v = solver.finalize(problem, carry)
    n = m_obs.shape[1]
    if l.shape[1] != n:  # ragged: trim the zero-padded tail columns
        l, s = l[:, :n], s[:, :n]
    return DCFResult(l=l, s=s, u=u, v=v, stats=stats)


def _solve_checkpointed(
    m_obs: Array,
    cfg: fz.DCFConfig,
    num_clients: int,
    key: Array,
    *,
    run: rt.RunConfig,
    warm: tuple[Array, Array] | None = None,
    mask: Array | None = None,
    participation: Array | float | None = None,
    faults: Array | None = None,
    checkpoint_dir: str | None = None,
    resume_from: str | None = None,
) -> DCFResult:
    """Host-driven sibling of :func:`_solve` with mid-solve snapshots:
    the fixed scan runs through :func:`repro.core.runtime.run_segmented`
    (bit-exact vs the single-scan driver, interruptions included)."""
    solver = make_solver(cfg, with_objective=run.needs_objective)
    problem = make_problem(m_obs, cfg, num_clients, key, warm, mask=mask,
                           participation=participation, faults=faults)
    carry, stats = rt.run_segmented(
        solver, problem, cfg.outer_iters, run,
        checkpoint_dir=checkpoint_dir, resume_from=resume_from,
    )
    l, s, u, v = jax.jit(solver.finalize)(problem, carry)
    n = m_obs.shape[1]
    if l.shape[1] != n:  # ragged: trim the zero-padded tail columns
        l, s = l[:, :n], s[:, :n]
    return DCFResult(l=l, s=s, u=u, v=v, stats=stats)


@partial(jax.jit, static_argnames=("cfg", "num_clients", "run"))
def _solve_batch(
    m_batch: Array,  # (B, m, n)
    cfg: fz.DCFConfig,
    num_clients: int,
    keys: Array,  # (B, 2) PRNG keys
    *,
    run: rt.RunConfig,
    warm: tuple[Array, Array] | None = None,  # ((B,m,r), (B,E,n_i,r))
    mask: Array | None = None,  # (B, m, n) per-problem observation masks
    participation: Array | float | None = None,  # shared (T, E) or rate
) -> DCFResult:
    problems = jax.vmap(
        lambda mo, k, w, om: make_problem(mo, cfg, num_clients, k, w,
                                          mask=om,
                                          participation=participation),
        in_axes=(0, 0, None if warm is None else 0,
                 None if mask is None else 0),
    )(m_batch, keys, warm, mask)
    (l, s, u, v), _, stats = rt.solve_batch(
        make_solver(cfg, with_objective=run.needs_objective),
        problems,
        cfg.outer_iters,
        run,
    )
    n = m_batch.shape[2]
    if l.shape[2] != n:  # ragged: trim the zero-padded tail columns
        l, s = l[:, :, :n], s[:, :, :n]
    return DCFResult(l=l, s=s, u=u, v=v, stats=stats)


# ---------------------------------------------------------------------------
# Registry adapters + legacy shims (repro.rpca front door)
# ---------------------------------------------------------------------------
def _resolve_num_clients(spec) -> int:
    """E from the spec, or inferred from a 2-D participation schedule."""
    if spec.num_clients is not None:
        return spec.num_clients
    part = spec.participation
    if part is not None and jnp.ndim(part) == 2:
        return jnp.shape(part)[1]
    raise ValueError(
        "method 'dcf' needs a client count: set RPCASpec.num_clients "
        "(or pass a (T, E) participation schedule to infer E from)"
    )


def _default_cfg(spec, name: str) -> fz.DCFConfig:
    rank = _rpca.require_rank(name, spec)
    part = spec.participation
    if part is not None:
        # A scalar rate sizes the elastic preset directly; for an explicit
        # (T, E) schedule use its mean participation when it is concrete
        # (under tracing fall back to the preset's reference rate).
        try:
            rate = float(jnp.mean(jnp.asarray(part, jnp.float32)))
        except (TypeError, jax.errors.TracerArrayConversionError):
            rate = 0.7
        return fz.DCFConfig.elastic(rank, participation=max(rate, 0.1))
    if spec.mask is not None:
        return fz.DCFConfig.masked(rank)
    return fz.DCFConfig.tuned(rank)


def _record_traffic(cfg: fz.DCFConfig, m: int, num_clients: int,
                    stats: rt.SolveStats) -> None:
    """Feed the process-wide consensus traffic counters (surfaced by
    ``RPCAService.metrics()``) with this solve's modelled wire bytes.
    The round count stays on the device until the counters are read."""
    from repro.distributed import multihost as mh

    rounds = stats.rounds
    if isinstance(rounds, jax.core.Tracer):  # traced: count the budget
        rounds = cfg.outer_iters
    mh.record_consensus(m, cfg.rank, num_clients, rounds,
                        cfg.consensus_compress, budget=cfg.outer_iters)


def _registry_make(spec, cfg, run_cfg):
    cfg = cfg if cfg is not None else _default_cfg(spec, "dcf")
    _rpca.require_cfg_type("dcf", cfg, fz.DCFConfig)
    num_clients = _resolve_num_clients(spec)
    key = _rpca.default_key(spec)
    # Host-side: inside the jitted solve the code table is a tracer, so
    # the value-dependent checks (delay x crash/flaky) must run here.
    validate.check_fault_plan(cfg, spec.faults, num_clients)
    checkpointed = (spec.checkpoint_dir is not None
                    or spec.resume_from is not None)
    if spec.batched:
        if spec.faults is not None:
            raise ValueError(
                "fault injection does not compose with batched solves: "
                "pass one problem per FaultPlan"
            )
        if checkpointed:
            raise ValueError(
                "mid-solve checkpointing does not compose with batched "
                "solves: checkpoint each problem separately"
            )
        res = _solve_batch(spec.m_obs, cfg, num_clients, key, run=run_cfg,
                           warm=spec.warm, mask=spec.mask,
                           participation=spec.participation)
    elif checkpointed:
        res = _solve_checkpointed(
            spec.m_obs, cfg, num_clients, key, run=run_cfg,
            warm=spec.warm, mask=spec.mask,
            participation=spec.participation,
            faults=flt.resolve_faults(spec.faults),
            checkpoint_dir=spec.checkpoint_dir,
            resume_from=spec.resume_from,
        )
    else:
        res = _solve(spec.m_obs, cfg, num_clients, key, run=run_cfg,
                     warm=spec.warm, mask=spec.mask,
                     participation=spec.participation,
                     faults=flt.resolve_faults(spec.faults))
    _record_traffic(cfg, spec.m_obs.shape[-2], num_clients, res.stats)
    return res.l, res.s, res.u, res.v, res.stats


def _registry_make_sharded(spec, cfg, run_cfg):
    cfg = cfg if cfg is not None else _default_cfg(spec, "dcf_sharded")
    _rpca.require_cfg_type("dcf_sharded", cfg, fz.DCFConfig)
    if spec.checkpoint_dir is not None or spec.resume_from is not None:
        res = _solve_sharded_checkpointed(
            spec.m_obs, cfg, spec.mesh,
            data_axes=spec.data_axes, model_axis=spec.model_axis,
            key=spec.key, run=run_cfg, warm=spec.warm, mask=spec.mask,
            participation=spec.participation, faults=spec.faults,
            checkpoint_dir=spec.checkpoint_dir,
            resume_from=spec.resume_from,
        )
    else:
        res = _solve_sharded(
            spec.m_obs, cfg, spec.mesh,
            data_axes=spec.data_axes, model_axis=spec.model_axis,
            key=spec.key, run=run_cfg, warm=spec.warm, mask=spec.mask,
            participation=spec.participation, faults=spec.faults,
        )
    num_clients = 1
    for a in spec.data_axes:
        num_clients *= spec.mesh.shape[a]
    _record_traffic(cfg, spec.m_obs.shape[0], num_clients, res.stats)
    return res.l, res.s, res.u, res.v, res.stats


_rpca.register_solver(
    "dcf",
    _rpca.SolverCaps(supports_mask=True, supports_factors=True,
                     supports_clients=True, supports_participation=True,
                     batchable=True, needs_rank=True, supports_lowp=True,
                     supports_robust_agg=True, supports_checkpoint=True),
    _registry_make,
)

_rpca.register_solver(
    "dcf_sharded",
    _rpca.SolverCaps(supports_mask=True, supports_factors=True,
                     supports_participation=True, supports_sharding=True,
                     batchable=False, needs_rank=True, supports_lowp=True,
                     supports_multiprocess=True, supports_robust_agg=True,
                     supports_checkpoint=True),
    _registry_make_sharded,
)


def dcf_pca(
    m_obs: Array,
    cfg: fz.DCFConfig,
    num_clients: int,
    key: Array | None = None,
    *,
    run: rt.RunConfig | str | None = None,
    warm: tuple[Array, Array] | None = None,
    mask: Array | None = None,
    participation: Array | float | None = None,
    faults: "flt.FaultPlan | Array | None" = None,
    checkpoint_dir: str | None = None,
    resume_from: str | None = None,
) -> DCFResult:
    """Run DCF-PCA with ``num_clients`` simulated clients on one device.

    ``mask`` (0/1, same shape as ``m_obs``) restricts every client's
    residual work to its observed entries (robust matrix completion).
    ``n % num_clients != 0`` is allowed: ragged columns are padded into
    equal slots behind a mask-zero plane and the consensus average is
    weighted by each client's true column count.  ``participation`` is a
    (T, E) 0/1 round schedule or a Bernoulli rate; dropped-out clients
    freeze their V_i and are excluded from that round's consensus.

    Thin shim over ``repro.rpca.solve(..., method="dcf")`` (bit-exact).
    """
    res = _rpca.solve(
        _rpca.RPCASpec(m_obs, mask=mask, warm=warm, key=key,
                       num_clients=num_clients,
                       participation=participation, faults=faults,
                       checkpoint_dir=checkpoint_dir,
                       resume_from=resume_from),
        method="dcf", run=run, cfg=cfg,
    )
    return DCFResult(l=res.l, s=res.s, u=res.u, v=res.v, stats=res.stats)


def dcf_pca_batch(
    m_batch: Array,  # (B, m, n)
    cfg: fz.DCFConfig,
    num_clients: int,
    keys: Array | None = None,  # (B, 2) PRNG keys
    *,
    run: rt.RunConfig | str | None = None,
    warm: tuple[Array, Array] | None = None,  # ((B,m,r), (B,E,n_i,r))
    mask: Array | None = None,  # (B, m, n) per-problem observation masks
    participation: Array | float | None = None,  # shared (T, E) or rate
) -> DCFResult:
    """Solve a stack of problems concurrently; finished problems freeze.

    ``participation`` is shared across the batch when it is a (T, E)
    schedule; a scalar rate draws an independent Bernoulli schedule per
    problem (from each problem's key).

    Alias for the front door's auto-detected batch route (the leading
    problem axis selects it); kept for signature compatibility.
    """
    return dcf_pca(m_batch, cfg, num_clients, keys, run=run, warm=warm,
                   mask=mask, participation=participation)


# ---------------------------------------------------------------------------
# Engine 2: SPMD over a device mesh (production path)
# ---------------------------------------------------------------------------
def _build_sharded(
    m_obs: Array,
    cfg: fz.DCFConfig,
    mesh: Mesh,
    *,
    data_axes: tuple[str, ...] = ("data",),
    model_axis: str | None = None,
    key: Array | None = None,
    run: rt.RunConfig | None = None,
    warm: tuple[Array, Array] | None = None,
    mask: Array | None = None,
    participation: Array | float | None = None,
    faults: "flt.FaultPlan | Array | None" = None,
    segment: tuple[int, int] | None = None,
    carry: dict | None = None,
    seg_final: bool = False,
):
    """DCF-PCA where each shard along ``data_axes`` is one paper "client".

    ``warm=(U, V)`` takes a replicated ``(m, r)`` consensus factor and a
    *global* ``(n, r)`` right factor (the sharded engine's own ``DCFResult``
    layout); the solve resumes the schedules at ``t0 = outer_iters`` like
    the simulated engine.

    * ``M`` sharded: rows over ``model_axis`` (optional), cols over
      ``data_axes`` -- P(model, data).
    * ``U`` consensus: row-sharded over model, replicated over data;
      one pmean over ``data_axes`` per round (Eq. 9).
    * ``V``: column-block-sharded over data, replicated over model
      (each model shard of a client needs full V_i rows).
    * When ``model_axis`` is set, the r x r Gram and the (n_i, r) inner
      contraction are psum-ed over it (DESIGN.md Sec. 8, item 3).
    * ``mask`` (0/1, shape of ``m_obs``) is sharded exactly like ``M`` --
      each client keeps its own slice of Omega and never communicates it;
      all residual work then runs over observed entries only.
    * Elastic topology: ``n % num_clients != 0`` zero-pads the column tail
      behind a mask-zero plane (each shard keeps an equal-size slot, the
      consensus weights use the true per-shard counts), and
      ``participation`` -- a replicated (T, E) 0/1 schedule or a Bernoulli
      rate -- turns the consensus pmean into a participation-weighted
      ``psum(w_i U_i)`` with ``w_i = p_i n_i / sum_j p_j n_j``.  The
      schedule is identical on every shard, so the runtime's early-exit
      predicate (computed on the consensus U) stays lock-step and the
      collectives never diverge.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    validate.check_consensus_cfg(cfg, participation)
    compress = cfg.consensus_compress
    delay = cfg.consensus_delay
    wire = compress is not None or bool(delay)
    if wire:
        from repro.distributed import grad_compress as gcomp
        from repro.distributed.grad_compress import (
            compressed_consensus_sum as gcomp_sum,
        )
        from repro.distributed.multihost import topk_k as mh_topk_k
    if cfg.pack_mask and mask is not None:
        # The mask plane is sharded exactly like M (P(model, data)); a
        # packed (m, n/8) plane would need its own sharding layout and
        # per-shard ragged byte boundaries.  Fail eagerly rather than
        # silently shipping dense mask traffic under a compact-plane flag.
        # (mask=None is fine: there is no plane to pack, matching the
        # simulated engine which packs only when a mask exists.)
        raise ValueError(
            "cfg.pack_mask is not supported by the sharded engine (the "
            "mask is sharded like M); use a dense mask, or the simulated "
            "engine for bit-packed planes"
        )
    run_cfg = run or rt.FIXED
    track = cfg.track_objective or run_cfg.needs_objective
    if mask is not None:
        validate.check_mask(mask, m_obs.shape)
        m_obs = mask * m_obs  # hidden entries must not influence the solve
    m, n = m_obs.shape
    # lam calibrates on the unpadded data (padding columns are not
    # observations).
    lam = (
        cfg.lam
        if cfg.lam is not None
        else fz.robust_lam(m_obs, mask=mask, sample=cfg.lam_sample)
    )
    num_clients = 1
    for a in data_axes:
        num_clients *= mesh.shape[a]
    ni_pad = -(-n // num_clients)
    n_pad = ni_pad * num_clients
    ragged = n_pad != n
    if ragged:
        base = mask if mask is not None else jnp.ones_like(m_obs)
        mask = jnp.pad(base, ((0, 0), (0, n_pad - n)))
        m_obs = jnp.pad(m_obs, ((0, 0), (0, n_pad - n)))
    n_frac = 1.0 / num_clients
    sched = _resolve_participation(
        participation, cfg.outer_iters, num_clients, key
    )
    validate.check_fault_plan(cfg, faults, num_clients)
    fault_tab = flt.resolve_faults(faults)
    if segment is not None and model_axis is not None:
        # The segmented carry rides replicated host arrays between calls;
        # a model-sharded U/err/pending would need per-process shard
        # reassembly.  Fail eagerly with the workaround spelled out.
        raise ValueError(
            "checkpointed (segmented) sharded solves do not compose with "
            "model_axis row sharding; shard only over data_axes, or solve "
            "without checkpointing"
        )

    row_spec = model_axis  # None => replicated rows
    m_sharding = NamedSharding(mesh, P(row_spec, data_axes))
    u_sharding = NamedSharding(mesh, P(row_spec, None))

    reduce_m = (
        (lambda x: jax.lax.psum(x, model_axis))
        if model_axis is not None
        else (lambda x: x)
    )
    all_axes = data_axes + ((model_axis,) if model_axis else ())

    k_u, k_v = jax.random.split(key)
    scale = 1.0 / float(jnp.sqrt(float(cfg.rank)))
    fdtype = jnp.result_type(m_obs.dtype, jnp.float32)  # factors stay f32
    # U init is identical across clients (the server broadcast); sharded
    # over rows only.  V_i inits are per-client (folded client index).
    if warm is None:
        t0 = 0
        u0 = jax.random.normal(k_u, (m, cfg.rank), fdtype) * scale
    else:
        # Eager full-shape validation (see the simulated engine): the
        # sharded engine's own DCFResult layout is ((m, r), (n, r)).
        u0, v_warm = validate.check_warm_shapes(
            warm, ("U", "V"), ((m, cfg.rank), (n, cfg.rank)),
            ("(m, rank)", "(n, rank)"),
        )
        if ragged:  # pad V's row tail like M's column tail
            v_warm = jnp.pad(v_warm, ((0, n_pad - n), (0, 0)))
        t0 = cfg.outer_iters  # resume, don't restart, the schedules

    def solve_body(m_local_full, u, v, w_local, sched_rep, fault_rep=None,
                   seg_extra=None):
        """shard_map body: this shard's (m_loc, n_i) block + its factors.
        ``w_local`` is this shard's mask slice (None when fully observed);
        ``sched_rep`` the replicated participation schedule (None = all);
        ``fault_rep`` the replicated (T_f, E) fault-code table (None =
        no injection) -- each shard reads its own column; ``seg_extra``
        the restored non-factor carry leaves in segmented execution."""
        idx = jax.lax.axis_index(data_axes)  # linear client index
        robust = cfg.aggregator != "weighted_mean"
        screen = cfg.divergence_screen

        def round_gates(t, u_i, u_prev):
            """This shard's (payload, consensus-weight, V-advance) for the
            round: the participation schedule composed with the fault plan
            at the consensus boundary (DESIGN.md Sec. 17).  Returns
            ``(u_i, pt, v_keep)`` with ``pt``/``v_keep`` None on the
            no-schedule, no-fault path."""
            pt_s = (
                sched_rep[jnp.mod(t, sched_rep.shape[0]), idx]
                if sched_rep is not None
                else jnp.float32(1.0)
            )
            if fault_rep is None:
                if sched_rep is None:
                    return u_i, None, None
                return u_i, pt_s, pt_s
            code = fault_rep[jnp.mod(t, fault_rep.shape[0]), idx]
            u_i = flt.corrupt_payload(code, u_i, u_prev)
            # Crash/flaky drop the vote; every fault but a crash ran the
            # local computation, so V_i advances (a dropped *message* must
            # not freeze local state).
            return u_i, pt_s * flt.live_mask(code), \
                pt_s * flt.v_advance_mask(code)
        if ragged:
            # True column count of this shard: the zero-padding sits at the
            # global tail, so shard i really owns clip(n - i*ni, 0, ni).
            n_i = jnp.clip(
                jnp.float32(n) - jnp.float32(ni_pad) * idx, 0.0,
                jnp.float32(ni_pad),
            )
            n_frac_i = n_i / jnp.float32(n)
        else:
            n_i = jnp.float32(1.0)  # uniform weight base
            n_frac_i = n_frac  # compile-time 1/E: legacy bit-exact path

        def plain_init(p):
            inf = jnp.asarray(jnp.inf, jnp.float32)
            return _Carry(u=p[0], v=p[1], diag=rt.Diag(inf, inf))

        def plain_step(p, c, t):
            t = t + t0
            eta = cfg.lr(t)
            lam_t = cfg.lam_at(lam, t)
            u_i, v_new, diag_i = fz.local_round(
                c.u, c.v, m_local_full, cfg=cfg, lam=lam_t, n_frac=n_frac_i,
                eta=eta, reduce_m=reduce_m, w=w_local,
            )
            u_i, pt, v_keep = round_gates(t, u_i, c.u)
            uniform = pt is None and not ragged
            # Consensus via the aggregator dispatch (machine-enforced:
            # RPCA-R006 flags any raw mean/pmean reintroduced here).
            u_new, wsum = fz.aggregate_sharded(
                cfg, u_i, c.u, axes=data_axes,
                pt=jnp.float32(1.0) if pt is None else pt, n_i=n_i,
                uniform=uniform, reduce_m=reduce_m,
            )
            if v_keep is not None:
                # Dropped / crashed this round: the client's V_i freezes
                # (no decay toward zero weight).
                v_new = jnp.where(v_keep > 0, v_new, c.v)
            if not track:
                obj = jnp.zeros((), jnp.float32)
            elif (diag_i is not None and sched_rep is None
                  and fault_rep is None):
                # Fused epilogue data term (already summed over this
                # shard's rows; the model axis holds distinct rows, so the
                # all-axes psum composes it exactly like local_objective).
                obj = jax.lax.psum(
                    diag_i[0]
                    + fz.reg_terms(u_new, v_new, cfg.rho, n_frac_i),
                    all_axes,
                )
            else:
                # Participation rounds keep the legacy pass: a dropped
                # shard's epilogue measured a discarded local run.
                obj = jax.lax.psum(
                    fz.local_objective(
                        u_new, v_new, m_local_full, cfg.rho, lam_t,
                        n_frac_i, w=w_local,
                    ),
                    all_axes,
                )
            # Residual on the consensus U: psum the squared norms over the
            # model axis so every shard sees the same scalar and the
            # while_loop predicate (and hence the collectives) stay
            # lock-step across the mesh.
            du2 = reduce_m(jnp.sum((u_new - c.u) ** 2))
            u2 = reduce_m(jnp.sum(c.u**2))
            resid = jnp.sqrt(du2) / (jnp.sqrt(u2) + 1e-30)
            if wsum is not None:
                # All-dropout round (possible in user-supplied schedules):
                # a no-op round re-emits the previous residual (zero would
                # read as convergence) and an inf objective (the frozen
                # state would trivially plateau); wsum is a psum, so every
                # shard agrees and the early exit stays lock-step.
                resid = jnp.where(wsum > 0, resid, c.diag.residual)
                if track:
                    obj = jnp.where(wsum > 0, obj, jnp.inf)
            return _Carry(u=u_new, v=v_new, diag=rt.Diag(obj, resid))

        def wire_init(p):
            inf = jnp.asarray(jnp.inf, jnp.float32)
            c = {"u": p[0], "v": p[1], "diag": rt.Diag(inf, inf)}
            if compress is not None:
                c["err"] = jnp.zeros(p[0].shape, jnp.float32)
            if delay:
                c["pending"] = jnp.zeros(p[0].shape, jnp.float32)
                c["sync"] = jnp.zeros((), jnp.bool_)
                c["guard"] = inf
            return c

        def wire_step(p, c, t):
            # Consensus-wire variant (DESIGN.md Sec. 14): the consensus is
            # delta-form -- each shard's weighted delta crosses the wire
            # (top-k compressed with error feedback when configured) and
            # may be applied one round late under consensus_delay.
            tg = t + t0
            eta = cfg.lr(tg)
            lam_t = cfg.lam_at(lam, tg)
            u_used = c["u"]
            u_i, v_new, diag_i = fz.local_round(
                u_used, c["v"], m_local_full, cfg=cfg, lam=lam_t,
                n_frac=n_frac_i, eta=eta, reduce_m=reduce_m, w=w_local,
            )
            u_i, pt, v_keep = round_gates(tg, u_i, u_used)
            with jax.named_scope(fz.CONSENSUS_SCOPE):
                wsum = None
                if robust:
                    # One unweighted vote per client: the robust combine is
                    # over raw deltas, weights would let one client scale its
                    # own influence.
                    wgt = jnp.float32(1.0)
                    if v_keep is not None:
                        v_new = jnp.where(v_keep > 0, v_new, c["v"])
                elif pt is None and not ragged:
                    wgt = jnp.float32(1.0 / num_clients)
                else:
                    ptw = jnp.float32(1.0) if pt is None else pt
                    u_i = jnp.where(ptw > 0, u_i, u_used)
                    if v_keep is not None:
                        v_new = jnp.where(v_keep > 0, v_new, c["v"])
                    raw_w = ptw * n_i
                    wsum = jax.lax.psum(raw_w, data_axes)
                    wgt = raw_w / jnp.maximum(wsum, 1e-30)
                contrib = (wgt * (u_i - u_used)).astype(jnp.float32)
                act = jnp.float32(1.0) if pt is None else pt
                out = dict(c)
                if compress is None:
                    if robust or screen is not None:
                        # Dense robust/screened consensus via the aggregator
                        # dispatch (RPCA-R006); applied delta-form so the
                        # delay/pending machinery composes unchanged.
                        u_cand, wsum = fz.aggregate_sharded(
                            cfg, u_i, u_used, axes=data_axes, pt=act, n_i=n_i,
                            uniform=False, reduce_m=reduce_m,
                        )
                        delta = (u_cand - u_used).astype(jnp.float32)
                    else:
                        delta = jax.lax.psum(contrib, data_axes)
                else:
                    # Wire-compact collective: one all-gather of the compact
                    # (k values, k int32 indices) payloads over the data axes
                    # -- E k * 8 bytes on the wire instead of the dense
                    # m r * 4 all-reduce -- and a deterministic scatter-add,
                    # identical on every shard (lock-step preserved).  Each
                    # model-axis shard compresses its own row block.
                    k = mh_topk_k(u_used.size, compress.topk_frac)
                    if robust:
                        # Same wire format, robust receive: per-client
                        # reconstructions are combined one-vote instead of
                        # scatter-summed; a poisoned payload must not poison
                        # the error-feedback carry forever, so non-finite
                        # residuals reset to zero.
                        delta, err_new, cnt = (
                            gcomp.compressed_consensus_robust(
                                contrib, data_axes, k, c["err"], active=act,
                                aggregator=cfg.aggregator,
                                trim_frac=cfg.trim_frac, screen=screen,
                                reduce_m=reduce_m,
                            ))
                        wsum = cnt.astype(jnp.float32)
                        err_new = jnp.where(jnp.isfinite(err_new),
                                            err_new, 0.0)
                    else:
                        delta, err_new = gcomp_sum(
                            contrib, data_axes, k, c["err"], active=pt)
                    out["err"] = err_new
                if delay == 0:
                    u_new = u_used + delta
                    # All-dropout round: delta is an exact zero (every weight
                    # is 0 / every payload shipped zeros), so u_new == c.u.
                else:
                    # Staleness guard: the fused epilogue's ||Psi||_F^2 psum
                    # (free since PR 5) -- or the consensus-step energy when
                    # fused="off" -- trips a sticky fallback to synchronous
                    # application on divergence.  Both scalars are psum/
                    # reduce_m-composed, so every shard agrees and the
                    # collectives stay lock-step.
                    if diag_i is not None and fault_rep is None:
                        scalar = jax.lax.psum(diag_i[1], all_axes)
                    else:
                        # Fault rounds guard on the *applied* delta energy: the
                        # fused epilogue measured the uncorrupted local run and
                        # would never see an injected payload blow-up.
                        scalar = reduce_m(jnp.sum(delta * delta))
                    # Trip on guard-factor growth OR a non-finite scalar (NaN
                    # compares False, so the growth test alone never fires on
                    # a hard blowup).
                    trip = jnp.logical_or(
                        ~jnp.isfinite(scalar),
                        jnp.isfinite(c["guard"])
                        & (scalar > cfg.stale_guard * c["guard"]),
                    )
                    sync = jnp.logical_or(c["sync"], trip)
                    u_new = u_used + c["pending"] + jnp.where(
                        sync, delta, jnp.zeros_like(delta))
                    out["pending"] = jnp.where(sync, jnp.zeros_like(delta),
                                               delta)
                    out["sync"] = sync
                    out["guard"] = scalar
            if not track:
                obj = jnp.zeros((), jnp.float32)
            elif (diag_i is not None and sched_rep is None
                  and fault_rep is None):
                obj = jax.lax.psum(
                    diag_i[0]
                    + fz.reg_terms(u_new, v_new, cfg.rho, n_frac_i),
                    all_axes,
                )
            else:
                obj = jax.lax.psum(
                    fz.local_objective(
                        u_new, v_new, m_local_full, cfg.rho, lam_t,
                        n_frac_i, w=w_local,
                    ),
                    all_axes,
                )
            du2 = reduce_m(jnp.sum((u_new - u_used) ** 2))
            u2 = reduce_m(jnp.sum(u_used**2))
            resid = jnp.sqrt(du2) / (jnp.sqrt(u2) + 1e-30)
            if delay:
                # Round 0 applies nothing (its delta is pending): re-emit
                # the previous residual instead of a convergence-faking 0.
                resid = jnp.where(t > 0, resid, c["diag"].residual)
            if wsum is not None:
                resid = jnp.where(wsum > 0, resid, c["diag"].residual)
                if track:
                    obj = jnp.where(wsum > 0, obj, jnp.inf)
            out["u"] = u_new
            out["v"] = v_new
            out["diag"] = rt.Diag(obj, resid)
            return out

        if segment is not None:
            # Checkpoint-segmented execution: scan the [t_start, t_start +
            # seg_len) slice of the *global* round sequence from a restored
            # carry -- the per-round math is identical to rt.run's fixed
            # scan, so segment boundaries never perturb the trajectory.
            t_start, seg_len = segment
            if wire:
                c0 = wire_init((u, v))
            else:
                c0 = plain_init((u, v))
            if seg_extra is not None:
                dg = rt.Diag(seg_extra["dobj"], seg_extra["dres"])
                if wire:
                    for kk in ("err", "pending", "sync", "guard"):
                        if kk in seg_extra:
                            c0[kk] = seg_extra[kk]
                    c0["diag"] = dg
                else:
                    c0 = _Carry(u=c0.u, v=c0.v, diag=dg)

            @rt.in_round
            def seg_body(c, t):
                c = (wire_step if wire else plain_step)((u, v), c, t)
                return c, (c["diag"] if wire else c.diag)

            carry, diags = jax.lax.scan(
                seg_body, c0, t_start + jnp.arange(seg_len)
            )
            if not seg_final:
                if wire:
                    out = dict(carry)
                    dg = out.pop("diag")
                else:
                    out = {"u": carry.u, "v": carry.v}
                    dg = carry.diag
                # The carry crosses segments as replicated host arrays:
                # gather the column-sharded V into its global layout (the
                # E blocks concatenate in client-index order).
                from repro.distributed import grad_compress as _gc

                out["v"] = _gc.gather_clients(
                    out["v"], data_axes
                ).reshape(n_pad, cfg.rank)
                if "err" in out:
                    # The error-feedback residual is *per-client* state
                    # (each shard drops different top-k coordinates):
                    # stack it client-major like V so every client's
                    # residual survives the replicated hand-off.
                    out["err"] = _gc.gather_clients(
                        out["err"], data_axes
                    ).reshape(-1, cfg.rank)
                out["dobj"] = dg.objective
                out["dres"] = dg.residual
                return out, diags.objective, diags.residual
            if wire:
                u_fin = (carry["u"] + carry["pending"] if delay
                         else carry["u"])
                v_fin = carry["v"]
            else:
                u_fin, v_fin = carry.u, carry.v
            l_blk, s_blk = fz.finalize(
                u_fin, v_fin, m_local_full, cfg.final_lam(lam), cfg.impl,
                w=w_local,
            )
            return (l_blk, s_blk, u_fin, v_fin, diags.objective,
                    diags.residual)
        if wire:
            solver = rt.Solver(wire_init, wire_step,
                               lambda p, c: c["diag"], lambda p, c: None)
        else:
            solver = rt.Solver(plain_init, plain_step,
                               lambda p, c: c.diag, lambda p, c: None)
        carry, stats = rt.run(solver, (u, v), cfg.outer_iters, run_cfg)
        if wire:
            # Flush the in-flight stale delta; the last consensus step
            # must not be dropped.
            u_fin = carry["u"] + carry["pending"] if delay else carry["u"]
            v_fin = carry["v"]
        else:
            u_fin, v_fin = carry.u, carry.v
        l_blk, s_blk = fz.finalize(
            u_fin, v_fin, m_local_full, cfg.final_lam(lam), cfg.impl,
            w=w_local,
        )
        return l_blk, s_blk, u_fin, v_fin, stats

    if segment is None:
        specs_out = (
            P(row_spec, data_axes),  # L
            P(row_spec, data_axes),  # S
            P(row_spec, None),  # U
            P(data_axes, None),  # V
            rt.SolveStats(  # replicated telemetry
                objective=P(None), residual=P(None), rounds=P(),
                converged=P()
            ),
        )
    elif seg_final:
        specs_out = (
            P(row_spec, data_axes),  # L
            P(row_spec, data_axes),  # S
            P(row_spec, None),  # U
            P(data_axes, None),  # V
            P(None),  # segment objective trace
            P(None),  # segment residual trace
        )
    else:
        # Mid-solve carry: every leaf leaves the mesh replicated (V is
        # gathered in-body), so each process can lift a full host copy
        # for the checkpoint writer.
        carry_specs = {"u": P(None, None), "v": P(None, None),
                       "dobj": P(), "dres": P()}
        if compress is not None:
            carry_specs["err"] = P(None, None)  # gathered client-major
        if delay:
            carry_specs["pending"] = P(None, None)
            carry_specs["sync"] = P()
            carry_specs["guard"] = P()
        specs_out = (carry_specs, P(None), P(None))
    # Pack the (static-keyed) operand dict so the mask x warm combinations
    # share one shard_map body; absent keys are simply not in the pytree.
    multiproc = len({d.process_index for d in mesh.devices.flat}) > 1

    def _put(x, sharding):
        # A cross-process sharding needs host-side operands: every process
        # holds the full array (the solve entrypoints are SPMD -- each
        # process ran the same padding/calibration on the same input) and
        # device_put places only its addressable shards.
        return jax.device_put(np.asarray(x) if multiproc else x, sharding)

    args = {"m": _put(m_obs, m_sharding),
            "u": _put(u0, u_sharding)}
    specs = {"m": P(row_spec, data_axes), "u": P(row_spec, None)}
    if mask is not None:
        args["w"] = _put(mask, m_sharding)
        specs["w"] = P(row_spec, data_axes)
    if warm is not None:
        args["v"] = _put(
            v_warm, NamedSharding(mesh, P(data_axes, None))
        )
        specs["v"] = P(data_axes, None)
    if sched is not None:
        # The schedule is replicated: every shard indexes the same (T, E)
        # table, so the round's participation set (and hence the weighted
        # consensus and the early-exit predicate) agrees mesh-wide.
        args["sched"] = _put(
            sched, NamedSharding(mesh, P(None, None))
        )
        specs["sched"] = P(None, None)
    if fault_tab is not None:
        # The fault table is replicated like the schedule: every shard
        # reads its own column of the same (T_f, E) table, so the round's
        # fault set agrees mesh-wide and the collectives stay lock-step.
        args["faults"] = _put(
            fault_tab, NamedSharding(mesh, P(None, None))
        )
        specs["faults"] = P(None, None)
    seg_keys = ()
    if carry is not None:
        # Resume a segmented solve: the factor leaves re-enter through the
        # ordinary sharded operand slots (U replicated, V column-sliced);
        # the wire leaves and the last round's diagnostics ride replicated.
        args["u"] = _put(carry["u"], u_sharding)
        args["v"] = _put(
            carry["v"], NamedSharding(mesh, P(data_axes, None))
        )
        specs["v"] = P(data_axes, None)
        rep = NamedSharding(mesh, P(None, None))
        rep0 = NamedSharding(mesh, P())
        seg_keys = tuple(
            k for k in ("err", "pending", "sync", "guard", "dobj", "dres")
            if k in carry
        )
        for k in seg_keys:
            if k == "err":
                # Client-major stacked residual: slice each client's
                # (rows, r) block back onto its own shard.
                args[k] = _put(
                    carry[k], NamedSharding(mesh, P(data_axes, None))
                )
                specs[k] = P(data_axes, None)
                continue
            scalar = jnp.ndim(carry[k]) == 0
            args[k] = _put(carry[k], rep0 if scalar else rep)
            specs[k] = P() if scalar else P(None, None)

    def solve(packed):
        m_local_full = packed["m"]
        if "v" in packed:
            v = packed["v"]
        else:
            # Cold start: per-client V_i from a client-folded key.
            n_i = m_local_full.shape[1]
            idx = jax.lax.axis_index(data_axes)
            kv_local = jax.random.fold_in(k_v, idx)
            v = (
                jax.random.normal(
                    kv_local, (n_i, cfg.rank),
                    jnp.result_type(m_local_full.dtype, jnp.float32),
                ) * scale
            )
        seg_extra = (
            {k: packed[k] for k in seg_keys} if seg_keys else None
        )
        return solve_body(m_local_full, packed["u"], v, packed.get("w"),
                          packed.get("sched"), packed.get("faults"),
                          seg_extra)

    fn = jax.shard_map(solve, mesh=mesh, in_specs=(specs,),
                       out_specs=specs_out, check_vma=False)
    return fn, args, n, ragged


def _solve_sharded(
    m_obs: Array,
    cfg: fz.DCFConfig,
    mesh: Mesh,
    **kwargs,
) -> DCFResult:
    """Execute the sharded solve (see :func:`_build_sharded`)."""
    fn, args, n, ragged = _build_sharded(m_obs, cfg, mesh, **kwargs)
    l, s, u, v, stats = jax.jit(fn)(args)
    if ragged:  # trim the zero-padded tail columns / V rows
        l, s, v = l[:, :n], s[:, :n], v[:n]
    return DCFResult(l=l, s=s, u=u, v=v, stats=stats)


def _host(x) -> np.ndarray:
    """Full host copy of a replicated global array -- multi-process safe
    (``device_get`` would reject non-addressable shards; a replicated
    array's first addressable shard *is* the full value)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return np.asarray(x.addressable_data(0))
    return np.asarray(jax.device_get(x))


def _solve_sharded_checkpointed(
    m_obs: Array,
    cfg: fz.DCFConfig,
    mesh: Mesh,
    *,
    run: rt.RunConfig | None = None,
    checkpoint_dir: str | None = None,
    resume_from: str | None = None,
    **kwargs,
) -> DCFResult:
    """Sharded solve with mid-solve carry snapshots (DESIGN.md Sec. 17).

    The fixed scan is split into host-driven shard_map segments over the
    global round indices -- bit-exact with :func:`_solve_sharded` -- and
    after each segment every process holds a full replicated host copy of
    the solver carry (wire error-feedback residuals, pending stale deltas
    and guard scalars included); process 0 writes it through
    ``training.checkpoint``'s atomic-manifest machinery.  ``resume_from``
    restores the latest snapshot (rejecting a changed mesh shape with a
    clear error) and finishes the remaining rounds, so a killed worker
    respawned on the same topology reproduces the uninterrupted solve
    bit-for-bit.
    """
    from repro.training import checkpoint as ckpt

    run_cfg = run or rt.FIXED
    if run_cfg.mode != "scan":
        raise ValueError(
            f"checkpointed solves require run mode 'scan' (the fixed "
            f"paper schedule); got mode {run_cfg.mode!r}"
        )
    mesh_shape = [int(s) for s in np.shape(mesh.devices)]
    total = cfg.outer_iters
    t_done = 0
    carry_host: dict | None = None
    obuf = np.zeros((0,), np.float32)
    rbuf = np.zeros((0,), np.float32)
    if resume_from is not None:
        step = ckpt.latest_step(resume_from)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {resume_from}")
        restored, t_done = ckpt.restore(
            resume_from, _sharded_ckpt_template(cfg), step=step,
            expect_mesh=mesh_shape,
        )
        carry_host = {k: np.asarray(v) for k, v in
                      restored["carry"].items()}
        obuf = np.asarray(restored["objective"], np.float32)
        rbuf = np.asarray(restored["residual"], np.float32)
        if t_done > total:
            raise ValueError(
                f"checkpoint at round {t_done} exceeds this solve's "
                f"budget of {total} rounds"
            )
    plan = rt.segment_plan(total - t_done, run_cfg.checkpoint_every)
    if not plan:  # resumed at the budget's end: nothing left to run
        raise ValueError(
            f"checkpoint already covers all {total} rounds; nothing to "
            f"resume (finalize needs at least one remaining segment)"
        )
    for i, seg in enumerate(plan):
        final = i == len(plan) - 1
        fn, args, n, ragged = _build_sharded(
            m_obs, cfg, mesh, run=run_cfg, segment=(t_done, seg),
            carry=carry_host, seg_final=final, **kwargs,
        )
        out = jax.jit(fn)(args)
        t_done += seg
        if final:
            l, s, u, v = out[:4]
            obuf = np.concatenate([obuf, _host(out[4])])
            rbuf = np.concatenate([rbuf, _host(out[5])])
            break
        carry_dev, obj_seg, res_seg = out
        carry_host = {k: _host(x) for k, x in carry_dev.items()}
        obuf = np.concatenate([obuf, _host(obj_seg)])
        rbuf = np.concatenate([rbuf, _host(res_seg)])
        if checkpoint_dir is not None and jax.process_index() == 0:
            ckpt.save(
                checkpoint_dir, t_done,
                {"carry": carry_host, "objective": obuf,
                 "residual": rbuf},
                mesh_shape=mesh_shape,
            )
    stats = rt.SolveStats(
        objective=jnp.asarray(obuf),
        residual=jnp.asarray(rbuf),
        rounds=jnp.asarray(total, jnp.int32),
        converged=rt.scan_converged(run_cfg, jnp.asarray(obuf),
                                    jnp.asarray(rbuf)),
    )
    if ragged:  # trim the zero-padded tail columns / V rows
        l, s, v = l[:, :n], s[:, :n], v[:n]
    return DCFResult(l=l, s=s, u=u, v=v, stats=stats)


def _sharded_ckpt_template(cfg: fz.DCFConfig) -> dict:
    """Structure template for restoring a sharded segment checkpoint
    (leaf shapes come from the manifest; only the tree shape matters)."""
    z = jnp.zeros((), jnp.float32)
    carry = {"u": z, "v": z, "dobj": z, "dres": z}
    if cfg.consensus_compress is not None:
        carry["err"] = z
    if cfg.consensus_delay:
        carry["pending"] = z
        carry["sync"] = z
        carry["guard"] = z
    return {"carry": carry, "objective": z, "residual": z}


def sharded_solve_hlo(
    m_obs: Array,
    cfg: fz.DCFConfig,
    mesh: Mesh,
    *,
    optimized: bool = True,
    **kwargs,
) -> str:
    """Optimized HLO text of the jitted sharded solve, without running it
    (``optimized=False``: the lowered program, without compiling).

    This is the *measured* side of the consensus wire model: the bench
    (``benchmarks/consensus_bench.py``) feeds it to
    ``roofline.hlo_costs.analyze_hlo`` and reads the collective bytes the
    compiled program actually moves per solve -- dense all-reduce vs
    top-k all-gather -- rather than trusting the analytic byte model.
    """
    fn, args, _, _ = _build_sharded(m_obs, cfg, mesh, **kwargs)
    lowered = jax.jit(fn).lower(args)
    return lowered.compile().as_text() if optimized else lowered.as_text()


def dcf_pca_sharded(
    m_obs: Array,
    cfg: fz.DCFConfig,
    mesh: Mesh,
    *,
    data_axes: tuple[str, ...] = ("data",),
    model_axis: str | None = None,
    key: Array | None = None,
    run: rt.RunConfig | str | None = None,
    warm: tuple[Array, Array] | None = None,
    mask: Array | None = None,
    participation: Array | float | None = None,
    faults: "flt.FaultPlan | Array | None" = None,
    checkpoint_dir: str | None = None,
    resume_from: str | None = None,
) -> DCFResult:
    """SPMD DCF-PCA over ``mesh`` (see :func:`_solve_sharded` for the
    sharding layout and elastic-topology semantics).

    Thin shim over ``repro.rpca.solve(..., method="dcf_sharded")``
    (bit-exact).
    """
    res = _rpca.solve(
        _rpca.RPCASpec(m_obs, mask=mask, warm=warm, key=key, mesh=mesh,
                       data_axes=data_axes, model_axis=model_axis,
                       participation=participation, faults=faults,
                       checkpoint_dir=checkpoint_dir,
                       resume_from=resume_from),
        method="dcf_sharded", run=run, cfg=cfg,
    )
    return DCFResult(l=res.l, s=res.s, u=res.u, v=res.v, stats=res.stats)
