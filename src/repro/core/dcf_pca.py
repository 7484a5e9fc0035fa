"""DCF-PCA -- Algorithm 1: distributed RPCA via consensus factorization.

One round body, :func:`client_step`, is what one client does in a round:
its local iterations, the participation/fault gate, the consensus
(``factorized.aggregate_sharded``, with the optional top-k wire and stale
application) and the round's objective and residual.  Its collectives
name a client axis, and two engines map it over their clients:

``dcf_pca``          Simulated clients on one device: the E column blocks
                     live on a leading axis and the step runs under
                     ``jax.vmap(axis_name=CLIENTS)``.  A collective over
                     the mapped axis reduces over it and returns one
                     unbatched value, so the consensus ``pmean`` is the
                     stacked mean bit for bit and the consensus U, the
                     residual and the objective are computed once.  This
                     reproduces the paper's single-device simulation and
                     backs all paper experiments.

``dcf_pca_sharded``  SPMD engine: ``M`` is column-sharded over the mesh's
                     data axes (every shard is one "client") and optionally
                     row-sharded over the model axis; the step runs under
                     ``jax.shard_map``.  The consensus average is a single
                     ``lax.pmean`` of the (m, r) factor per round -- the
                     paper's 2 E m r communication bound, run as a
                     bandwidth-optimal ICI all-reduce.  V_i and S_i never
                     leave their shard (the privacy property).

``grad_compress.consensus_compress`` scans the same step over the
data-parallel axes of a training step.  Both engines run on the unified
solver runtime (DESIGN.md Sec. 4): pass ``run=`` for convergence-
controlled or chunked execution and ``warm=(U, V)`` to seed the factors
from a prior solve.  Client topology is elastic (DESIGN.md Sec. 10):
``n % num_clients != 0`` zero-pads ragged columns behind a mask plane and
weights the consensus by true per-client counts, and ``participation=``
(a (T, E) 0/1 schedule or Bernoulli rate) runs partial-participation
rounds -- dropped clients freeze their ``V_i`` and are excluded from that
round's weighted average.  The convergence residual is computed on the
*consensus* U (with a model-axis psum of the norms when rows are
sharded), so a ``lax.while_loop`` predicate is identical on every shard
and the collectives stay lock-step.
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
from repro.distributed import multihost as mh
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


#: Axis name of the simulated engine's clients under ``jax.vmap``.
CLIENTS = "clients"
#: Carry leaves each client holds alone; the rest is the consensus state,
#: the same on every client.
_CLIENT_LEAVES = ("v", "err")


def client_init(cfg: fz.DCFConfig, u: Array, v: Array,
                num_clients: int | None = None) -> dict:
    """The round carry: the consensus ``u``, this client's ``v`` and the
    last round's diagnostics, plus the wire state the config asks for --
    ``err`` (the top-k residual, one per client), and ``pending``,
    ``sync`` and ``guard`` (the stale delta and its guard).  A dict, so
    the extra state rides the runtime's generic pytree plumbing (batch
    freeze masks via ``tree_where`` included).  ``num_clients`` stacks
    ``err`` for the simulated engine, whose ``v`` is already stacked."""
    inf = jnp.asarray(jnp.inf, jnp.float32)
    c = {"u": u, "v": v, "diag": rt.Diag(inf, inf)}
    if cfg.consensus_compress is not None:
        lead = () if num_clients is None else (num_clients,)
        c["err"] = jnp.zeros(lead + u.shape, jnp.float32)
    if cfg.consensus_delay:
        c["pending"] = jnp.zeros(u.shape, jnp.float32)
        c["sync"] = jnp.zeros((), jnp.bool_)
        c["guard"] = inf
    return c


def _round_gate(sched: Array | None, faults: Array | None, t: Array, axes,
                u_i: Array, u_prev: Array):
    """This client's (payload, consensus weight, V-advance) for round
    ``t``: the participation schedule composed with the fault plan at the
    consensus boundary (DESIGN.md Sec. 17).  Both tables are replicated
    ``(T, E)``; each client reads its own column.  Returns ``(u_i, pt,
    v_keep)`` with ``pt``/``v_keep`` None on the no-schedule, no-fault
    path."""
    if sched is None and faults is None:
        return u_i, None, None
    idx = jax.lax.axis_index(axes)
    pt = (jnp.float32(1.0) if sched is None
          else sched[jnp.mod(t, sched.shape[0]), idx])
    if faults is None:
        return u_i, pt, pt
    code = faults[jnp.mod(t, faults.shape[0]), idx]
    u_i = flt.corrupt_payload(code, u_i, u_prev)
    # Crash/flaky drop the vote; every fault but a crash ran the local
    # computation, so V_i advances (a dropped *message* must not freeze
    # local state).
    return u_i, pt * flt.live_mask(code), pt * flt.v_advance_mask(code)


def client_step(
    cfg: fz.DCFConfig,
    c: dict,
    t: Array,
    *,
    block: Array,
    w: Array | None,
    n_i: Array | None,
    lam0: Array | float,
    t0: Array | int,
    sched: Array | None,
    faults: Array | None,
    axes,
    track: bool,
    model_axis: str | None = None,
) -> dict:
    """One DCF round (Alg. 1) as one client runs it; its collectives name
    the client ``axes``.  The simulated engine maps it over its stacked
    clients with ``jax.vmap(axis_name=CLIENTS)``, the sharded engine runs
    it under ``jax.shard_map`` over the mesh's data axes, and
    ``grad_compress.consensus_compress`` scans it over the data-parallel
    axes.  Consensus results (``u``, the residual, the objective, the
    wire's pending delta and guard) are the same on every client.

    ``block``/``w`` are this client's columns and mask slice, ``n_i`` its
    true column count (``None`` on equal blocks: the compile-time share
    ``1/E`` keeps the plain path bit-exact), ``sched``/``faults`` the
    replicated participation and fault tables (``None`` = none),
    ``model_axis`` the sharded engine's row axis (its partial sums are
    psum-ed over it).  ``t`` is the round within this solve; ``t0``
    offsets the schedules for warm-started resumes.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if model_axis is None:
        reduce_m, all_axes = fz._identity, axes
    else:
        reduce_m = partial(jax.lax.psum, axis_name=model_axis)
        all_axes = axes + (model_axis,)
    delay = cfg.consensus_delay
    tg = t + t0
    eta = cfg.lr(tg)
    lam_t = cfg.lam_at(lam0, tg)
    n_frac = (1.0 / jax.lax.axis_size(axes) if n_i is None
              else n_i / jax.lax.psum(n_i, axes))
    u_prev = c["u"]
    u_i, v_new, diag_i = fz.local_round(
        u_prev, c["v"], block, cfg=cfg, lam=lam_t, n_frac=n_frac, eta=eta,
        reduce_m=reduce_m, w=w,
    )
    u_i, pt, v_keep = _round_gate(sched, faults, tg, axes, u_i, u_prev)
    if v_keep is not None:
        # Dropped / crashed this round: the client's V_i freezes (no
        # decay toward zero weight).
        v_new = jnp.where(v_keep > 0, v_new, c["v"])
    out = dict(c)
    agg = dict(axes=axes, pt=jnp.float32(1.0) if pt is None else pt,
               n_i=jnp.float32(1.0) if n_i is None else n_i,
               uniform=pt is None and n_i is None, reduce_m=reduce_m)
    if cfg.consensus_compress is None:
        u_new, wsum = fz.aggregate_sharded(cfg, u_i, u_prev, **agg)
    else:
        u_new, wsum, out["err"] = fz.aggregate_sharded(
            cfg, u_i, u_prev, err=c["err"], **agg)
    if delay:
        with jax.named_scope(fz.CONSENSUS_SCOPE):
            # Stale application (DESIGN.md Sec. 14): this round's delta is
            # parked and applied next round.  The guard scalar is the
            # fused epilogue's ||Psi||_F^2 or, with fused="off" or under
            # faults (the epilogue measured the uncorrupted local run),
            # the consensus-step energy; growth past stale_guard x or a
            # non-finite value (NaN compares False) trips a sticky
            # fallback to synchronous application.
            delta = u_new - u_prev
            if diag_i is not None and faults is None:
                scalar = jax.lax.psum(diag_i[1], all_axes)
            else:
                scalar = reduce_m(jnp.sum(delta * delta))
            trip = jnp.logical_or(
                ~jnp.isfinite(scalar),
                jnp.isfinite(c["guard"])
                & (scalar > cfg.stale_guard * c["guard"]),
            )
            sync = jnp.logical_or(c["sync"], trip)
            zero = jnp.zeros_like(delta)
            u_new = u_prev + c["pending"] + jnp.where(sync, delta, zero)
            out["pending"] = jnp.where(sync, zero, delta)
            out["sync"] = sync
            out["guard"] = scalar
    if not track:
        obj = jnp.zeros((), jnp.float32)
    elif diag_i is not None and sched is None and faults is None:
        # Fused epilogue data term (already summed over this client's
        # rows; a model axis holds distinct rows, so the all-axes psum
        # composes it exactly like local_objective).
        obj = jax.lax.psum(
            diag_i[0] + fz.reg_terms(u_new, v_new, cfg.rho, n_frac),
            all_axes)
    else:
        # Participation/fault rounds take a separate pass: a dropped
        # client's epilogue measured a local run whose factors were then
        # discarded, so the frozen state's objective is the meaningful one.
        obj = jax.lax.psum(
            fz.local_objective(u_new, v_new, block, cfg.rho, lam_t, n_frac,
                               w=w),
            all_axes)
    # Residual on the consensus U (norms psum-ed over the model axis), so
    # every client sees the same scalar and a while_loop predicate -- and
    # hence the collectives -- stay lock-step.
    du2 = reduce_m(jnp.sum((u_new - u_prev) ** 2))
    u2 = reduce_m(jnp.sum(u_prev**2))
    resid = jnp.sqrt(du2) / (jnp.sqrt(u2) + 1e-30)
    if delay:
        # Round 0 applies nothing (its delta is pending): a zero residual
        # would read as instant convergence, so re-emit the previous.
        resid = jnp.where(t > 0, resid, c["diag"].residual)
    if wsum is not None:
        # All-dropout round (possible in user-supplied schedules): a no-op
        # that re-emits the previous residual (zero would read as
        # convergence) and an *inf* objective ("not measured": the frozen
        # state would trivially plateau), which suppresses the
        # obj_plateau check for this round and the next.  wsum is the
        # same on every client, so an early exit stays lock-step.
        resid = jnp.where(wsum > 0, resid, c["diag"].residual)
        if track:
            obj = jnp.where(wsum > 0, obj, jnp.inf)
    out["u"] = u_new
    out["v"] = v_new
    out["diag"] = rt.Diag(obj, resid)
    return out


def _flushed_u(cfg: fz.DCFConfig, c: dict) -> Array:
    """The final consensus U: the stale wire's in-flight delta is applied,
    so the last round's consensus step is not dropped."""
    return c["u"] + c["pending"] if cfg.consensus_delay else c["u"]


# ---------------------------------------------------------------------------
# Engine 1: simulated clients (paper Sec. 4.1 "Implementation")
# ---------------------------------------------------------------------------
def make_solver(cfg: fz.DCFConfig, *, with_objective: bool = False) -> rt.Solver:
    """Runtime Solver for the simulated-client engine: :func:`client_step`
    mapped over the stacked clients, whose collectives reduce over the
    mapped axis (the consensus is computed once, unbatched)."""
    track = cfg.track_objective or with_objective

    def init(p: DCFProblem) -> dict:
        return client_init(cfg, p.u_init, p.v_init, p.v_init.shape[0])

    def step(p: DCFProblem, c: dict, t: Array) -> dict:
        # The ``CLIENTS`` scope sits between the map and the step's own
        # scopes: a transform's name wraps the next name-stack component
        # (``vmap(rpca.consensus)``), and the trace readers match whole
        # ``rpca.*`` components.
        @jax.named_scope(CLIENTS)
        def mapped(c, block, w, n_i):
            return client_step(cfg, c, t, block=block, w=w, n_i=n_i,
                               lam0=p.lam0, t0=p.t0, sched=p.participation,
                               faults=p.faults, axes=CLIENTS, track=track)

        axes = {k: 0 if k in _CLIENT_LEAVES else None for k in c}
        return jax.vmap(mapped, in_axes=(axes, 0, 0, 0), out_axes=axes,
                        axis_name=CLIENTS)(c, p.blocks, p.mask, p.n_cols)

    def finalize(p: DCFProblem, c: dict):
        u = _flushed_u(cfg, c)
        l_blocks, s_blocks = jax.vmap(
            lambda vb, mb, wb: fz.finalize(
                u, vb, mb, cfg.final_lam(p.lam0), cfg.impl, w=wb
            )
        )(c["v"], p.blocks, p.mask)
        return (prob.merge_columns(l_blocks), prob.merge_columns(s_blocks),
                u, c["v"])

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

    def solve_body(block, u, v, w, sched, faults, seg_extra=None):
        """shard_map body: this shard's (m_loc, n_i) block + its factors.
        ``w`` is this shard's mask slice (None when fully observed);
        ``sched``/``faults`` the replicated participation schedule and
        (T_f, E) fault-code table (None = none); ``seg_extra`` the
        restored non-factor carry leaves in segmented execution."""
        n_i = None
        if ragged:
            # True column count of this shard: the zero-padding sits at the
            # global tail, so shard i really owns clip(n - i*ni, 0, ni).
            idx = jax.lax.axis_index(data_axes)
            n_i = jnp.clip(
                jnp.float32(n) - jnp.float32(ni_pad) * idx, 0.0,
                jnp.float32(ni_pad),
            )
        step = partial(client_step, cfg, block=block, w=w, n_i=n_i,
                       lam0=lam, t0=t0, sched=sched, faults=faults,
                       axes=data_axes, track=track, model_axis=model_axis)
        c0 = client_init(cfg, u, v)
        if segment is None:
            solver = rt.Solver(lambda p: c0, lambda p, c, t: step(c, t),
                               lambda p, c: c["diag"], lambda p, c: None)
            carry, stats = rt.run(solver, None, cfg.outer_iters, run_cfg)
            traces = (stats,)
        else:
            # Checkpoint-segmented execution: scan the [t_start, t_start +
            # seg_len) slice of the *global* round sequence from a restored
            # carry -- the per-round math is identical to rt.run's fixed
            # scan, so segment boundaries never perturb the trajectory.
            t_start, seg_len = segment
            if seg_extra is not None:
                c0.update({k: x for k, x in seg_extra.items()
                           if k in c0})
                c0["diag"] = rt.Diag(seg_extra["dobj"], seg_extra["dres"])

            @rt.in_round
            def seg_body(c, t):
                c = step(c, t)
                return c, c["diag"]

            carry, diags = jax.lax.scan(
                seg_body, c0, t_start + jnp.arange(seg_len)
            )
            traces = (diags.objective, diags.residual)
            if not seg_final:
                out = dict(carry)
                dg = out.pop("diag")
                # The carry crosses segments as replicated host arrays:
                # gather the column-sharded V into its global layout (the
                # E blocks concatenate in client-index order).
                out["v"] = fz.gather_clients(
                    out["v"], data_axes
                ).reshape(n_pad, cfg.rank)
                if "err" in out:
                    # The error-feedback residual is *per-client* state
                    # (each shard drops different top-k coordinates):
                    # stack it client-major like V so every client's
                    # residual survives the replicated hand-off.
                    out["err"] = fz.gather_clients(
                        out["err"], data_axes
                    ).reshape(-1, cfg.rank)
                out["dobj"] = dg.objective
                out["dres"] = dg.residual
                return (out,) + traces
        u_fin = _flushed_u(cfg, carry)
        l_blk, s_blk = fz.finalize(
            u_fin, carry["v"], block, cfg.final_lam(lam), cfg.impl, w=w,
        )
        return (l_blk, s_blk, u_fin, carry["v"]) + traces

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
        carry_specs = {
            k: P(None, None) if k in ("u", "v", "err", "pending") else P()
            for k in _sharded_ckpt_template(cfg)["carry"]
        }
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
    (leaf shapes come from the manifest; only the tree shape matters):
    the round carry, its diagnostics as ``dobj``/``dres``."""
    z = jnp.zeros((), jnp.float32)
    carry = {k: z for k in client_init(cfg, z, z) if k != "diag"}
    carry.update(dobj=z, dres=z)
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
