"""Unified solver runtime: one driver for every RPCA solver (DESIGN.md Sec. 4).

Every iterative solver in the stack (``apgm``, ``ialm``, ``cf_pca``, both
``dcf_pca`` engines) is expressed as a :class:`Solver` -- four pure
functions over an explicit ``problem`` pytree:

    init(problem)              -> carry          (cold or warm start)
    step(problem, carry, t)    -> carry          (one iteration / round)
    diagnostics(problem, carry)-> Diag           (objective + residual)
    finalize(problem, carry)   -> solver output  (e.g. (L, S) or (L, S, U, V))

and a single driver executes it under one of three modes
(:class:`RunConfig.mode`):

``scan``   Fixed-length ``lax.scan`` over ``max_iters`` -- the
           paper-faithful schedule, bit-identical to the pre-runtime
           hand-rolled loops.

``while``  Convergence-controlled ``lax.while_loop``: stop as soon as the
           criterion (relative residual or objective plateau) is met.
           Minimum dispatch per iteration; best for interactive /
           latency-sensitive solves.

``chunk``  ``lax.while_loop`` whose body is a ``chunk_size``-step
           ``lax.scan``: the jit-friendly serving mode.  Convergence is
           checked once per chunk, so the compiled program is a short
           static-shape loop body re-entered a dynamic number of times
           (exactly the decode-step pattern of ``serving/engine.py``).

Batching rides on the same protocol: :func:`solve_batch` vmaps a solver
over a leading problem axis and drives all problems in lock-step with a
per-problem convergence mask -- finished problems *freeze* (their carry
stops updating) while the rest keep iterating, and the loop exits when
every problem is done.  Warm-starting is a property of the ``problem``
pytree (it carries the initial factors), so a re-solve seeded with a prior
solution's ``(U, V)`` flows through every mode and through ``solve_batch``
unchanged.

Partial observation follows the same contract: an observation mask is a
``problem``-pytree leaf and every solver's ``diagnostics`` must be
computed on *observed* entries only (masked residual norms and objectives,
relative to ``||P_Omega(M)||``) -- the driver then needs no mask awareness
at all, and early exit / plateau detection / per-problem freeze masks stay
correct under masking, including heterogeneous per-problem masks in
``solve_batch`` (see DESIGN.md Sec. 9).

In-epilogue diagnostics (DESIGN.md Sec. 12): a solver's tracked objective
may be measured inside its last fused kernel pass (the factorized solvers'
dual-contraction epilogue emits the Huber data term and ``||Psi||_F^2``
with zero extra full-matrix passes) rather than by a dedicated pass over
the final state.  The contract this driver relies on is therefore
*consistency*, not a fixed evaluation point: each solver reports the same
well-defined surrogate every round (for the fused factorized rounds, the
client-summed ``g_i`` at the last fused pass's point -- half a U-step
stale under ``fused="diag"``, one further inner sweep stale under
``"dual"``; see ``factorized.local_round``), so ``obj_plateau`` deltas
and the recorded ``SolveStats.objective`` trace remain meaningful.  Solvers built with ``fused="off"`` keep the legacy
post-consensus objective pass; rounds where no progress was measurable
(all-dropout participation) still report an *inf* objective as below.

Elastic participation (DESIGN.md Sec. 10) extends that contract: a
participation schedule is another ``problem``-pytree leaf, the solver's
``step`` freezes dropped-out clients' local factors itself, and its
``residual`` diagnostic is computed on the *consensus* factor -- a
globally agreed scalar.  Two consequences keep the driver oblivious and
the execution lock-step: (1) in the SPMD engine every shard evaluates the
identical predicate (the schedule is replicated and the consensus U is
psum-ed), so a ``while``-mode early exit never strands a shard inside a
collective; (2) a round with zero participants must keep U unchanged
*without* reading as convergence: solvers re-emit the previous residual
(a zero would satisfy ``rel_residual``) and emit an *inf* objective
("not measured" -- the frozen state would trivially plateau), and the
``obj_plateau`` criterion requires two finite measurements.  Generated
schedules additionally guarantee >= 1 participant per round.
``solve_batch`` needs no awareness either way: per-problem schedules
ride the batch axis like masks do.

All drivers return a structured :class:`SolveStats` instead of the old
ad-hoc scalar ``history`` arrays.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Literal, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class Diag(NamedTuple):
    """Per-iteration diagnostics emitted by a solver.

    ``objective``  the solver's tracked objective value (0 when the solver
                   was built without objective tracking);
    ``residual``   the scalar convergence measure -- by convention a
                   *relative* quantity (factor change, constraint residual)
                   so a single tolerance is meaningful across solvers.
    """

    objective: Array
    residual: Array


class SolveStats(NamedTuple):
    """Structured solve telemetry (replaces the ad-hoc ``history`` array).

    ``objective``/``residual`` are ``(max_iters,)`` traces, zero-padded past
    ``rounds`` in the early-exit modes.  Under :func:`solve_batch` every
    field gains a leading batch axis.
    """

    objective: Array  # (T,) tracked objective per iteration
    residual: Array  # (T,) convergence residual per iteration
    rounds: Array  # () int32 -- iterations actually executed
    converged: Array  # () bool -- criterion met within the budget


class Solver(NamedTuple):
    """The solver protocol consumed by :func:`run` / :func:`solve_batch`.

    All four members are pure jit-traceable functions; ``problem`` is a
    pytree of arrays (the observed data plus initial factors), so the whole
    solver can be vmapped over a leading problem axis.
    """

    init: Callable[[Any], Any]
    step: Callable[[Any, Any, Array], Any]
    diagnostics: Callable[[Any, Any], Diag]
    finalize: Callable[[Any, Any], Any]


@dataclass(frozen=True)
class RunConfig:
    """Execution-mode knobs for the shared driver (static under jit).

    ``tol`` applies to ``criterion``: ``rel_residual`` stops when the
    solver's residual drops below ``tol``; ``obj_plateau`` stops when the
    objective changes by less than ``tol * max(1, |obj|)`` between checks
    (requires the solver to be built with objective tracking).
    ``min_iters`` suppresses spurious exits before the diagnostics settle.
    """

    mode: Literal["scan", "while", "chunk"] = "scan"
    tol: float = 1e-6
    criterion: Literal["rel_residual", "obj_plateau"] = "rel_residual"
    chunk_size: int = 8
    min_iters: int = 2
    #: Mid-solve snapshot cadence for :func:`run_segmented` (0 = only the
    #: implicit final segment; snapshots still require a checkpoint dir).
    #: Only meaningful in ``scan`` mode -- the segmented driver is
    #: bit-exact with the single fixed scan.
    checkpoint_every: int = 0

    @property
    def needs_objective(self) -> bool:
        return self.criterion == "obj_plateau"


#: Paper-faithful default: fixed-length scan, no early exit.
FIXED = RunConfig(mode="scan")

#: Convergence-controlled early exit (interactive / latency-sensitive
#: solves): stop as soon as the relative residual settles.
EARLY = RunConfig(mode="while")

#: Jit-friendly serving mode: static-shape chunked loop body re-entered a
#: dynamic number of times, convergence checked once per chunk.
CHUNKED = RunConfig(mode="chunk")

#: Named presets accepted anywhere a ``run=`` argument takes a string.
RUN_PRESETS: dict[str, RunConfig] = {
    "fixed": FIXED,
    "early": EARLY,
    "chunk": CHUNKED,
}


def resolve_run(run: "RunConfig | str | None") -> RunConfig:
    """Normalize a ``run=`` argument: ``None`` -> :data:`FIXED`, a string
    names a preset in :data:`RUN_PRESETS`, a :class:`RunConfig` passes
    through."""
    if run is None:
        return FIXED
    if isinstance(run, str):
        try:
            return RUN_PRESETS[run]
        except KeyError:
            raise ValueError(
                f"unknown run preset {run!r}; expected one of "
                f"{sorted(RUN_PRESETS)} or a RunConfig"
            ) from None
    if isinstance(run, RunConfig):
        return run
    raise ValueError(
        f"run must be a RunConfig, a preset name, or None; got "
        f"{type(run).__name__}"
    )


def _bcast(pred: Array, leaf: Array) -> Array:
    """Broadcast a ()- or (B,)-shaped predicate against a carry leaf."""
    extra = leaf.ndim - pred.ndim
    return jax.lax.reshape(pred, pred.shape + (1,) * extra) if extra else pred


def tree_where(pred: Array, new: Any, old: Any) -> Any:
    """``where(pred, new, old)`` over matching pytrees; ``pred`` is a scalar
    or a leading-axis mask (the batched freeze mask)."""
    return jax.tree.map(
        lambda a, b: jnp.where(_bcast(pred, a), a, b), new, old
    )


#: Name scope of one solver round in the compiled programs: a profiler
#: trace attributes every device operation traced inside it to the round.
ROUND_SCOPE = "rpca.round"


def in_round(fn: Callable) -> Callable:
    """``fn`` traced under :data:`ROUND_SCOPE` -- wraps every loop body
    that runs one round (the drivers here, the serving tick, the sharded
    engine's segments).  Op metadata only: the computation is unchanged."""
    return jax.named_scope(ROUND_SCOPE)(fn)


def _converged(run: RunConfig, diag: Diag, prev_obj: Array) -> Array:
    if run.criterion == "rel_residual":
        return diag.residual <= run.tol
    # A plateau requires two *finite* measurements: the pre-first-check
    # prev_obj is inf, a diverged solve's objective may be inf/nan, and
    # solvers emit an inf objective for rounds where no progress was
    # measurable (e.g. an all-dropout participation round) -- none of
    # those may read as convergence (inf <= tol * max(inf, 1) is True).
    delta_ok = jnp.abs(prev_obj - diag.objective) <= run.tol * jnp.maximum(
        jnp.abs(prev_obj), 1.0
    )
    return delta_ok & jnp.isfinite(prev_obj) & jnp.isfinite(diag.objective)


def _f32(x) -> Array:
    return jnp.asarray(x, jnp.float32)


# ---------------------------------------------------------------------------
# Single-problem driver
# ---------------------------------------------------------------------------
def run(
    solver: Solver,
    problem: Any,
    max_iters: int,
    run_cfg: RunConfig = FIXED,
) -> tuple[Any, SolveStats]:
    """Drive ``solver`` on one problem; returns ``(final_carry, stats)``.

    Callers apply ``solver.finalize`` themselves (wrappers often need the
    raw carry, e.g. to hand factors back for warm-starting).
    """
    carry0 = solver.init(problem)
    if run_cfg.mode == "scan":
        return _run_scan(solver, problem, carry0, max_iters, run_cfg)
    if run_cfg.mode == "while":
        return _run_while(solver, problem, carry0, max_iters, run_cfg)
    if run_cfg.mode == "chunk":
        return _run_chunk(solver, problem, carry0, max_iters, run_cfg)
    raise ValueError(f"unknown mode {run_cfg.mode!r}")


def driver(
    solver: Solver,
    max_iters: int,
    run_cfg: RunConfig = FIXED,
) -> Callable[[Any], tuple[Any, SolveStats]]:
    """Close ``(solver, budget, run mode)`` into a pure
    ``drive(problem) -> (final_carry, stats)`` program.

    This is the AOT-compilable unit behind the compile cache (DESIGN.md
    Sec. 13): everything static lives in the closure, everything dynamic
    rides the ``problem`` pytree, so one ``jax.jit(...).lower(...)
    .compile()`` per run preset covers every problem of that shape.
    Equivalent to ``lambda p: run(solver, p, max_iters, run_cfg)`` -- the
    regular jit path traces the identical computation.
    """

    def drive(problem: Any) -> tuple[Any, SolveStats]:
        return run(solver, problem, max_iters, run_cfg)

    return drive


def scan_converged(run_cfg: RunConfig, obuf: Array, rbuf: Array) -> Array:
    """The fixed-scan convergence verdict from completed diag traces --
    shared by :func:`_run_scan` and the segmented (checkpointing) drivers
    so an interrupted+resumed solve reports the identical flag."""
    last = Diag(obuf[-1], rbuf[-1])
    prev_obj = obuf[-2] if obuf.shape[0] > 1 else _f32(jnp.inf)
    return _converged(run_cfg, last, prev_obj)


def segment_plan(max_iters: int, checkpoint_every: int) -> list[int]:
    """Split ``max_iters`` rounds into checkpoint segments.

    ``checkpoint_every <= 0`` means one segment (no mid-solve snapshots);
    otherwise equal segments of that length with a ragged tail.  At most
    two distinct lengths, so the jitted segment body compiles at most
    twice.
    """
    if checkpoint_every <= 0 or checkpoint_every >= max_iters:
        return [max_iters] if max_iters > 0 else []
    full, tail = divmod(max_iters, checkpoint_every)
    return [checkpoint_every] * full + ([tail] if tail else [])


def run_segmented(
    solver: Solver,
    problem: Any,
    max_iters: int,
    run_cfg: RunConfig = FIXED,
    *,
    checkpoint_dir: str | None = None,
    resume_from: str | None = None,
    save_extra: Callable[[int, Any], None] | None = None,
) -> tuple[Any, SolveStats]:
    """Checkpointing sibling of :func:`run` (scan mode only): the fixed
    scan is split into host-driven segments of
    ``run_cfg.checkpoint_every`` rounds, each a jitted ``lax.scan`` over
    the *global* round indices -- bit-exact with the single-scan driver,
    segment boundaries included.

    After every segment the full solver carry plus the diagnostics traces
    so far are written through ``training.checkpoint``'s atomic-manifest
    machinery (when ``checkpoint_dir`` is set); ``resume_from`` restores
    the latest snapshot in that directory and finishes the remaining
    rounds, reproducing the uninterrupted solve bit-for-bit (the carry is
    the *entire* solver state: wire error-feedback residuals, pending
    stale deltas and guard scalars ride along).  ``save_extra(t, carry)``
    is an optional post-save hook (e.g. process-0 gating upstream).
    """
    if run_cfg.mode != "scan":
        raise ValueError(
            f"checkpointed solves require run mode 'scan' (the fixed "
            f"paper schedule); got mode {run_cfg.mode!r}"
        )
    from repro.training import checkpoint as ckpt

    @jax.jit
    def _init(problem):
        return solver.init(problem)

    def _segment(problem, carry, ts):
        @in_round
        def body(c, t):
            c = solver.step(problem, c, t)
            return c, solver.diagnostics(problem, c)

        return jax.lax.scan(body, carry, ts)

    seg_fn = jax.jit(_segment)
    t_done = 0
    obuf = jnp.zeros((0,), jnp.float32)
    rbuf = jnp.zeros((0,), jnp.float32)
    carry = _init(problem)
    if resume_from is not None:
        # Restore into the cold-start structure: a leaf-count mismatch (a
        # different solver config) fails with checkpoint.py's clear error
        # rather than deep inside the scan.  Trace-buffer lengths come
        # from the manifest, so the zero-length templates are fine.
        template = {
            "carry": carry,
            "objective": jnp.zeros((0,), jnp.float32),
            "residual": jnp.zeros((0,), jnp.float32),
        }
        restored, t_done = ckpt.restore(resume_from, template)
        carry = restored["carry"]
        obuf = restored["objective"]
        rbuf = restored["residual"]
        if t_done > max_iters:
            raise ValueError(
                f"checkpoint at round {t_done} exceeds this solve's "
                f"budget of {max_iters} rounds"
            )
    for seg in segment_plan(max_iters - t_done, run_cfg.checkpoint_every):
        ts = t_done + jnp.arange(seg)
        carry, diags = seg_fn(problem, carry, ts)
        t_done += seg
        obuf = jnp.concatenate([obuf, _f32(diags.objective)])
        rbuf = jnp.concatenate([rbuf, _f32(diags.residual)])
        if checkpoint_dir is not None and t_done < max_iters:
            ckpt.save(
                checkpoint_dir, t_done,
                {"carry": carry, "objective": obuf, "residual": rbuf},
            )
            if save_extra is not None:
                save_extra(t_done, carry)
    stats = SolveStats(
        objective=obuf,
        residual=rbuf,
        rounds=jnp.asarray(max_iters, jnp.int32),
        converged=scan_converged(run_cfg, obuf, rbuf),
    )
    return carry, stats


def _run_scan(solver, problem, carry0, max_iters, run_cfg):
    @in_round
    def body(c, t):
        c = solver.step(problem, c, t)
        return c, solver.diagnostics(problem, c)

    carry, diags = jax.lax.scan(body, carry0, jnp.arange(max_iters))
    last = Diag(diags.objective[-1], diags.residual[-1])
    prev_obj = diags.objective[-2] if max_iters > 1 else _f32(jnp.inf)
    stats = SolveStats(
        objective=diags.objective,
        residual=diags.residual,
        rounds=jnp.asarray(max_iters, jnp.int32),
        converged=_converged(run_cfg, last, prev_obj),
    )
    return carry, stats


def _run_while(solver, problem, carry0, max_iters, run_cfg):
    buf = jnp.zeros((max_iters,), jnp.float32)
    init = (
        carry0,
        jnp.zeros((), jnp.int32),
        Diag(_f32(jnp.inf), _f32(jnp.inf)),
        _f32(jnp.inf),
        buf,
        buf,
    )

    def cond(st):
        _, t, last, prev_obj, _, _ = st
        done = _converged(run_cfg, last, prev_obj) & (t >= run_cfg.min_iters)
        return (t < max_iters) & ~done

    @in_round
    def body(st):
        c, t, last, prev_obj, obuf, rbuf = st
        c = solver.step(problem, c, t)
        d = solver.diagnostics(problem, c)
        obuf = obuf.at[t].set(_f32(d.objective))
        rbuf = rbuf.at[t].set(_f32(d.residual))
        return c, t + 1, d, last.objective, obuf, rbuf

    carry, t, last, prev_obj, obuf, rbuf = jax.lax.while_loop(cond, body, init)
    stats = SolveStats(
        objective=obuf,
        residual=rbuf,
        rounds=t,
        converged=_converged(run_cfg, last, prev_obj),
    )
    return carry, stats


def _run_chunk(solver, problem, carry0, max_iters, run_cfg):
    chunk = max(1, run_cfg.chunk_size)
    n_chunks = -(-max_iters // chunk)
    padded = n_chunks * chunk
    buf = jnp.zeros((padded,), jnp.float32)
    init = (
        carry0,
        jnp.zeros((), jnp.int32),
        Diag(_f32(jnp.inf), _f32(jnp.inf)),
        _f32(jnp.inf),
        buf,
        buf,
    )

    def cond(st):
        _, t, last, prev_obj, _, _ = st
        done = _converged(run_cfg, last, prev_obj) & (t >= run_cfg.min_iters)
        return (t < max_iters) & ~done

    def body(st):
        c, t, last, prev_obj, obuf, rbuf = st

        @in_round
        def inner(cc, i):
            g = t + i
            c_new = solver.step(problem, cc, g)
            # Freeze the tail of the last (ragged) chunk past max_iters.
            cc = tree_where(g < max_iters, c_new, cc)
            return cc, solver.diagnostics(problem, cc)

        c, diags = jax.lax.scan(inner, c, jnp.arange(chunk))
        obuf = jax.lax.dynamic_update_slice(obuf, _f32(diags.objective), (t,))
        rbuf = jax.lax.dynamic_update_slice(rbuf, _f32(diags.residual), (t,))
        d = Diag(diags.objective[-1], diags.residual[-1])
        return c, t + chunk, d, last.objective, obuf, rbuf

    carry, t, last, prev_obj, obuf, rbuf = jax.lax.while_loop(cond, body, init)
    stats = SolveStats(
        objective=obuf[:max_iters],
        residual=rbuf[:max_iters],
        rounds=jnp.minimum(t, max_iters),
        converged=_converged(run_cfg, last, prev_obj),
    )
    return carry, stats


# ---------------------------------------------------------------------------
# Batched driver: lock-step rounds with per-problem freeze masks
# ---------------------------------------------------------------------------
def solve_batch(
    solver: Solver,
    problems: Any,
    max_iters: int,
    run_cfg: RunConfig = FIXED,
) -> tuple[Any, Any, SolveStats]:
    """Solve a batch of problems concurrently with one vmapped program.

    ``problems`` is the solver's problem pytree with a leading batch axis
    on every leaf.  All problems advance in lock-step; under the early-exit
    criteria each problem that converges is *frozen* (its carry and
    diagnostics stop changing, its ``rounds`` counter stops) while the
    stragglers keep iterating, and the loop exits once all are done (or at
    ``max_iters``).  ``mode='scan'`` runs the full fixed budget with no
    convergence checks -- batched results are then the vmapped image of the
    serial solves.

    Returns ``(results, final_carry, stats)`` where ``results`` is the
    vmapped ``solver.finalize`` output and every ``stats`` field has a
    leading batch axis.
    """
    leaves = jax.tree.leaves(problems)
    if not leaves:
        raise ValueError("solve_batch needs a non-empty problem pytree")
    batch = leaves[0].shape[0]

    init_b = jax.vmap(solver.init)
    step_b = jax.vmap(solver.step, in_axes=(0, 0, None))
    diag_b = jax.vmap(solver.diagnostics)
    fin_b = jax.vmap(solver.finalize)

    check = run_cfg.mode != "scan"
    carry0 = init_b(problems)
    obuf = jnp.zeros((batch, max_iters), jnp.float32)
    init = (
        carry0,
        jnp.zeros((), jnp.int32),  # global lock-step round counter
        jnp.zeros((batch,), bool),  # per-problem done mask
        jnp.zeros((batch,), jnp.int32),  # per-problem executed rounds
        Diag(jnp.full((batch,), jnp.inf, jnp.float32),
             jnp.full((batch,), jnp.inf, jnp.float32)),
        jnp.full((batch,), jnp.inf, jnp.float32),  # prev objective
        obuf,
        obuf,
    )

    def cond(st):
        _, t, done, *_ = st
        return (t < max_iters) & ~jnp.all(done)

    @in_round
    def body(st):
        c, t, done, rounds, last, prev_obj, obuf, rbuf = st
        c_new = step_b(problems, c, t)
        c = tree_where(~done, c_new, c)  # finished problems freeze
        d_new = diag_b(problems, c)
        d = Diag(
            jnp.where(done, last.objective, _f32(d_new.objective)),
            jnp.where(done, last.residual, _f32(d_new.residual)),
        )
        active = ~done
        obuf = obuf.at[:, t].set(jnp.where(active, d.objective, 0.0))
        rbuf = rbuf.at[:, t].set(jnp.where(active, d.residual, 0.0))
        rounds = rounds + active.astype(jnp.int32)
        if check:
            hit = _converged(run_cfg, d, prev_obj) & (
                rounds >= run_cfg.min_iters
            )
            done = done | (active & hit)
        prev_obj = jnp.where(active, d.objective, prev_obj)
        return c, t + 1, done, rounds, d, prev_obj, obuf, rbuf

    carry, _, done, rounds, *_, obuf, rbuf = jax.lax.while_loop(
        cond, body, init
    )
    if not check:
        # Fixed scan: mirror the serial driver and evaluate the criterion
        # on the final diagnostics instead of reporting all-False.
        last = Diag(obuf[:, -1], rbuf[:, -1])
        prev_obj = (
            obuf[:, -2]
            if max_iters > 1
            else jnp.full((batch,), jnp.inf, jnp.float32)
        )
        done = _converged(run_cfg, last, prev_obj)
    stats = SolveStats(
        objective=obuf, residual=rbuf, rounds=rounds, converged=done
    )
    return fin_b(problems, carry), carry, stats
