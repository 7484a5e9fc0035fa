"""Slot-based batched RPCA serving endpoint (DESIGN.md Sec. 7, Sec. 11).

Continuous-batching lite, mirroring ``serving/engine.py``'s design: a fixed
batch of request *slots* advances in lock-step through vmapped,
jit-compiled solver programs; each tick runs ``rounds_per_tick`` rounds
for every in-flight problem.  Per-slot convergence masks freeze finished
problems (their carry stops updating) so one slow tenant never burns
compute for the rest, and the caller refills freed slots between ticks --
exactly the decode-slot lifecycle of the LM engine.

Built on the ``repro.rpca`` solver registry: every registered method whose
capability record has ``supports_service`` can back a slot (today ``cf``,
``apgm``, ``ialm``), and ``submit(m_obs, method=...)`` picks the solver
*per request*.  Each method in use gets a *lane* -- its own homogeneous
batched problem pytree and jitted tick program over the service's slot
table -- because different solvers carry different state; slots remain one
global namespace, so the ``submit / tick / poll / release`` lifecycle is
method-oblivious.

Warm-starting is first-class: ``submit(m_obs, warm=...)`` seeds a slot
from a prior solution -- ``(U, V)`` factors for the factorized lane
(resuming the annealing schedule), ``(L, S)`` iterates for the convex
lanes -- so streaming refresh solves (same tenant, slightly changed data)
converge in a handful of rounds instead of the full budget.

Partial observation is per-slot: ``submit(m_obs, mask=omega)`` attaches a
0/1 observation mask and the whole solve runs over observed entries only.
Maskless submissions get an all-ones mask plane (the slot pytrees must be
homogeneous), which is bit-exact with the unmasked solver path for the
``cf`` lane and numerically identical for the convex ones.

    svc = RPCAService(m, n, DCFConfig.tuned(rank=8))
    slot = svc.submit(m_obs, mask=omega)               # cf (default)
    tiny = svc.submit(m_small, method="ialm")          # convex lane
    while svc.pending():
        svc.tick()
    resp = svc.poll(slot)          # RPCAResponse(l, s, u, v, rounds, ...)
    svc.release(slot)
    # streaming refresh: warm factors + the epoch's evolved mask
    slot = svc.submit(m_obs_new, warm=(resp.u, resp.v), mask=omega_new)
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import rpca as _rpca
from repro.core import compile_cache as cc
from repro.core import runtime as rt
from repro.core import validate
from repro.core.factorized import DCFConfig

Array = jax.Array

#: Entries kept in each service's robust_lam calibration cache (tiny:
#: a 16-byte fingerprint pair -> one float per distinct tenant plane).
_LAM_CACHE_CAP = 128


def _fingerprint(x: Any) -> bytes | None:
    """Content fingerprint of one data/mask plane (shape + dtype +
    bytes); ``None`` stays ``None`` so (M, mask) pairs key cleanly."""
    if x is None:
        return None
    arr = np.ascontiguousarray(np.asarray(x))
    h = hashlib.blake2b(digest_size=16)
    h.update(str(arr.dtype).encode())
    h.update(np.asarray(arr.shape, np.int64).tobytes())
    h.update(arr.tobytes())
    return h.digest()


@dataclass(frozen=True)
class RPCAServiceConfig:
    """Service knobs (static: changing them recompiles the ticks)."""

    slots: int = 8  # concurrent in-flight problems
    rounds_per_tick: int = 8  # solver rounds per jitted tick
    max_rounds: int = 200  # per-problem round budget
    tol: float = 5e-4  # rel-residual convergence tolerance
    min_rounds: int = 2  # suppress spurious first-round exits


class RPCAResponse(NamedTuple):
    l: Array  # recovered low-rank matrix (m, n)
    s: Array  # recovered sparse matrix (m, n)
    u: Array | None  # left factor (m, r) -- reuse as warm start (cf lane)
    v: Array | None  # right factor (n, r); None for the convex lanes
    rounds: int  # solver rounds actually spent
    converged: bool  # met the tolerance (False => ran out of max_rounds)
    method: str = "cf"  # which registered solver ran this slot
    #: The slot's residual went non-finite mid-solve (poisoned input or a
    #: numerically divergent iterate): the slot was quarantined -- frozen
    #: and marked done -- at that round so its NaNs never touch the other
    #: tenants' lock-step planes.  ``l``/``s`` are whatever the iterate
    #: held (typically non-finite); the gateway maps this to a typed
    #: :class:`~repro.core.validate.SolverDiverged` failure.
    diverged: bool = False


class _Lane:
    """One registered method's slot-table state: a homogeneous batched
    problem pytree, its carry, and a jitted lock-step tick program."""

    def __init__(self, method: str, hooks: _rpca.ServiceHooks, cfg: Any,
                 scfg: RPCAServiceConfig, m: int, n: int):
        self.method = method
        self.hooks = hooks
        self.cfg = cfg
        self.solver = hooks.make_solver(cfg)
        self.problems = hooks.empty_problems(cfg, scfg.slots, m, n)
        self.carry = jax.vmap(self.solver.init)(self.problems)

        step_b = jax.vmap(self.solver.step, in_axes=(0, 0, 0))
        diag_b = jax.vmap(self.solver.diagnostics)

        def tick(problems, carry, t, done, rounds, hit, dived, lane_active):
            """rounds_per_tick lock-step rounds with per-slot freeze.

            ``lane_active`` masks this lane's occupied slots; slots owned
            by other lanes (or free) never advance, so the global per-slot
            counters can be shared across lanes.

            A slot whose residual goes non-finite is *quarantined*: it is
            marked done (and ``dived``) at that round, so its frozen NaN
            carry stops advancing and -- because every per-slot update is
            already masked by ``adv`` -- never leaks into a neighbor's
            plane.  The lane keeps ticking for everyone else.
            """

            @rt.in_round
            def body(st, _):
                carry, t, done, rounds, hit, dived = st
                adv = lane_active & ~done
                carry = rt.tree_where(adv, step_b(problems, carry, t), carry)
                d = diag_b(problems, carry)
                t = t + adv.astype(jnp.int32)
                rounds = rounds + adv.astype(jnp.int32)
                bad = adv & ~jnp.isfinite(d.residual)
                hit_now = (d.residual <= scfg.tol) & (
                    rounds >= scfg.min_rounds
                )
                hit = hit | (adv & hit_now)
                dived = dived | bad
                done = done | bad | (
                    adv & (hit_now | (rounds >= scfg.max_rounds))
                )
                return (carry, t, done, rounds, hit, dived), None

            (carry, t, done, rounds, hit, dived), _ = jax.lax.scan(
                body, (carry, t, done, rounds, hit, dived), None,
                length=scfg.rounds_per_tick,
            )
            return carry, t, done, rounds, hit, dived

        # Donate the per-tick state (carry + slot counters): every tick
        # consumes the previous tick's buffers, so XLA reuses them in place
        # instead of double-buffering the (slots, m, n) residual planes of
        # the convex lanes on every call.  The problem pytree (arg 0) is
        # NOT donated -- it persists across ticks and submits write into it.
        #
        # All lane executables come AOT-compiled from the process-wide
        # compile cache (DESIGN.md Sec. 13): lanes sharing a solver and
        # slot geometry -- across services too -- reuse one tick /
        # finalize / slot-write program instead of compiling per lane.
        cache = cc.default_cache()
        b = scfg.slots

        def _z(dt):
            return jnp.zeros((b,), dt)

        self._tick = cache.get(
            ("service_tick", method, cfg, scfg, m, n),
            lambda: jax.jit(tick, donate_argnums=(1, 2, 3, 4, 5, 6)).lower(
                self.problems, self.carry, _z(jnp.int32), _z(bool),
                _z(jnp.int32), _z(bool), _z(bool), _z(bool),
            ).compile(),
            cc.AOT,
        )
        one_p = jax.tree.map(lambda a: a[0], self.problems)
        one_c = jax.tree.map(lambda a: a[0], self.carry)
        self._finalize_one = cache.get(
            ("service_finalize", method, cfg, m, n),
            lambda: jax.jit(self.solver.finalize).lower(
                one_p, one_c
            ).compile(),
            cc.AOT,
        )

    def write_slot(self, batched: Any, single: Any, idx: Array) -> Any:
        """``batched.at[idx].set(single)`` over a pytree, through the
        shared compile cache -- the executable is keyed purely on the
        pytree structure + leaf signature, so the problem- and
        carry-shaped writers of every same-geometry lane (and service)
        each compile exactly once process-wide."""

        def _write(batched, single, i):
            return jax.tree.map(
                lambda b_, x: b_.at[i].set(x), batched, single
            )

        key = (
            "service_write_slot",
            jax.tree.structure((batched, single)),
            cc.arg_signature((batched, single, idx)),
        )
        exe = cc.default_cache().get(
            key,
            lambda: jax.jit(_write).lower(batched, single, idx).compile(),
            cc.AOT,
        )
        return exe(batched, single, idx)


class RPCAService:
    """Batched multi-tenant RPCA solves over ``scfg.slots`` request slots.

    ``method`` is the default lane for submissions; ``cfg`` is its solver
    config.  Other service-capable methods are available per-request via
    ``submit(..., method=...)``; their configs come from ``cfgs`` (falling
    back to the registry's default config for that method).
    """

    def __init__(
        self,
        m: int,
        n: int,
        cfg: DCFConfig,
        scfg: RPCAServiceConfig = RPCAServiceConfig(),
        key: Array | None = None,
        method: str = "cf",
        cfgs: dict[str, Any] | None = None,
    ):
        self.cfg = cfg
        self.scfg = scfg
        self.m = m
        self.n = n
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._n_submitted = 0
        self._default_method = method
        self._cfgs = dict(cfgs or {})
        self._cfgs.setdefault(method, cfg)

        b = scfg.slots
        self._t = jnp.zeros((b,), jnp.int32)  # per-slot schedule position
        self._rounds = jnp.zeros((b,), jnp.int32)
        self._done = jnp.zeros((b,), bool)
        self._hit = jnp.zeros((b,), bool)  # met the tolerance (vs budget-out)
        self._dived = jnp.zeros((b,), bool)  # quarantined: non-finite residual
        self._active = np.zeros((b,), bool)  # host-side slot occupancy
        self._slot_n = np.full((b,), n, np.int64)  # true width per slot
        self._slot_method = [method] * b  # lane owning each slot
        # lam-cache fingerprint held by each slot (None = the slot's cfg
        # does not calibrate); release() evicts the entry when the last
        # slot holding a fingerprint departs.
        self._slot_lam_fp: list[tuple | None] = [None] * b

        # robust_lam calibration cache: (M fingerprint, mask fingerprint)
        # -> calibrated lam.  Warm refreshes of unchanged tenant data skip
        # the full-matrix sorts (PR-5: the 20-round refresh e2e is lam-
        # calibration dominated).
        self._lam_cache: "OrderedDict[tuple, float]" = OrderedDict()
        self._lam_hits = 0
        self._lam_misses = 0

        self._lanes: dict[str, _Lane] = {}
        self._lane(method)  # build the default lane eagerly

    # -- lanes ---------------------------------------------------------------
    def _lane(self, method: str) -> _Lane:
        lane = self._lanes.get(method)
        if lane is not None:
            return lane
        entry = _rpca.get_solver(method)
        if entry.service is None or not entry.caps.supports_service:
            raise ValueError(
                f"method {method!r} does not support the slot service; "
                f"service methods: "
                f"{', '.join(_rpca.methods_with('supports_service'))}"
            )
        cfg = self._cfgs.get(method)
        if cfg is None:
            if entry.service.default_cfg is None:
                raise ValueError(
                    f"service lane {method!r} needs a config: pass "
                    f"cfgs={{{method!r}: ...}} to RPCAService"
                )
            cfg = entry.service.default_cfg()
            self._cfgs[method] = cfg
        if entry.service.cfg_type is not None:
            # Eager: a cfg/method mismatch otherwise dies deep inside the
            # lane's solver construction with an AttributeError.
            _rpca.require_cfg_type(method, cfg, entry.service.cfg_type)
        lane = _Lane(method, entry.service, cfg, self.scfg, self.m, self.n)
        self._lanes[method] = lane
        return lane

    # -- request lifecycle --------------------------------------------------
    def validate_submission(
        self,
        m_obs: Array,
        warm: tuple[Array, Array] | None = None,
        mask: Array | None = None,
        method: str | None = None,
    ) -> tuple[str, int]:
        """Run the *never-valid* admission checks without consuming a
        slot: method service support, row count / width fit, mask shape,
        warm-factor shapes.  Returns the resolved ``(method, n_req)``.

        The async gateway calls this at ``submit()`` time so a doomed
        request raises ``ValueError`` at the caller instead of queueing
        and failing its future at admission.
        """
        method = method or self._default_method
        lane = self._lane(method)  # validates method before shape checks
        n_req = validate.check_service_problem(m_obs, self.m, self.n)
        validate.check_mask(mask, m_obs.shape)
        if warm is not None:
            warm = validate.check_warm_pair(warm)
            layout = lane.hooks.warm_layout(lane.cfg, self.m, n_req)
            for w, (name, shape, desc, _) in zip(warm, layout):
                validate.check_factor(w, shape, name, desc)
        return method, n_req

    def free_slots(self) -> int:
        """Host-side free-slot count (no device sync)."""
        return int((~self._active).sum())

    def try_submit(
        self,
        m_obs: Array,
        warm: tuple[Array, Array] | None = None,
        mask: Array | None = None,
        method: str | None = None,
    ) -> int:
        """Place a problem into a free slot; returns the slot id.

        Admission is typed: a problem that can never fit (wrong row
        count, too many columns, mis-shaped mask or warm factors, a
        method without service support) raises ``ValueError`` eagerly,
        while a *full* slot table raises
        :class:`~repro.core.validate.CapacityError` -- transient, retry
        after a tick + poll + release cycle.  The async gateway maps the
        latter to queue backpressure (``QueueFull``).

        ``method`` picks the registered solver for *this* request (default:
        the service's default lane).  ``warm`` is lane-shaped: ``(U, V)``
        factors for ``cf``, ``(L, S)`` iterates for the convex lanes.

        ``mask`` is this request's observation mask (0/1, shape of
        ``m_obs``); it may differ from the mask of the warm-start's prior
        solve -- streaming refreshes re-solve under the current epoch's
        observation pattern.

        Ragged widths are first-class: an ``(m, n_req)`` problem with
        ``n_req < n`` is zero-padded into the service's homogeneous
        ``(m, n)`` slot pytree behind a mask-zero plane (the PR-2 Omega
        plumbing) and :meth:`poll` trims the response back to ``n_req``.
        """
        method, n_req = self.validate_submission(m_obs, warm, mask, method)
        lane = self._lanes[method]
        layout = lane.hooks.warm_layout(lane.cfg, self.m, n_req)
        if warm is not None:
            warm = validate.check_warm_pair(warm)
        free = np.flatnonzero(~self._active)
        if free.size == 0:
            raise validate.service_at_capacity(self.scfg.slots)
        slot = int(free[0])
        key = jax.random.fold_in(self._key, self._n_submitted)
        self._n_submitted += 1
        # lam calibration cache: fingerprint the *submitted* (pre-pad)
        # planes -- only for configs that actually sort the data for lam
        # (the factorized family with lam=None); the convex lanes derive
        # lam from the shape for free.  ``fp_key`` is remembered per slot
        # (hit or miss) so release() can refcount-evict the entry.
        cfg_sub, fp_key, lam_fp = lane.cfg, None, None
        if isinstance(lane.cfg, DCFConfig) and lane.cfg.lam is None:
            fp_key = (_fingerprint(m_obs), _fingerprint(mask))
            lam_hit = self._lam_cache.get(fp_key)
            if lam_hit is not None:
                self._lam_cache.move_to_end(fp_key)
                self._lam_hits += 1
                cfg_sub = dataclasses.replace(lane.cfg, lam=lam_hit)
            else:
                self._lam_misses += 1
                lam_fp = fp_key  # freshly calibrated below: store it
        if n_req < self.n:
            # Ragged width: pad the data (and the mask's base plane) with
            # mask-zero columns so the padded tail never influences the
            # solve; lam still calibrates on the real columns only (the
            # masked-median path ignores mask-zero entries).
            pad = self.n - n_req
            base = mask if mask is not None else jnp.ones_like(m_obs)
            mask = jnp.pad(base, ((0, 0), (0, pad)))
            m_obs = jnp.pad(m_obs, ((0, 0), (0, pad)))
            if warm is not None:
                warm = tuple(
                    w if ax is None else jnp.pad(
                        w, [(0, pad) if a == ax else (0, 0)
                            for a in range(w.ndim)]
                    )
                    for w, (_, _, _, ax) in zip(warm, layout)
                )
        problem = lane.hooks.make_problem(m_obs, cfg_sub, key, warm, mask)
        if lam_fp is not None:
            # Freshly calibrated: remember it for the next refresh of the
            # same (M, mask) pair.  lam0 calibrates identically on the
            # padded plane (masked medians ignore mask-zero entries).
            self._lam_cache[lam_fp] = float(problem.lam0)
            while len(self._lam_cache) > _LAM_CACHE_CAP:
                self._lam_cache.popitem(last=False)
        self._slot_n[slot] = n_req
        self._slot_method[slot] = method
        self._slot_lam_fp[slot] = fp_key
        idx = jnp.asarray(slot)
        lane.problems = lane.write_slot(lane.problems, problem, idx)
        lane.carry = lane.write_slot(
            lane.carry, lane.solver.init(problem), idx
        )
        self._t = self._t.at[slot].set(0)
        self._rounds = self._rounds.at[slot].set(0)
        self._done = self._done.at[slot].set(False)
        self._hit = self._hit.at[slot].set(False)
        self._dived = self._dived.at[slot].set(False)
        self._active[slot] = True
        return slot

    def submit(
        self,
        m_obs: Array,
        warm: tuple[Array, Array] | None = None,
        mask: Array | None = None,
        method: str | None = None,
    ) -> int | None:
        """Legacy admission shim: like :meth:`try_submit`, but returns
        ``None`` when the batch is full instead of raising.

        .. deprecated::
            The ``None``-on-capacity return conflates "no result" with a
            typed, retryable condition; it is kept for existing callers
            (with a ``DeprecationWarning`` on the capacity path only).
            New code calls :meth:`try_submit` and handles
            :class:`~repro.core.validate.CapacityError`.
        """
        try:
            return self.try_submit(m_obs, warm, mask=mask, method=method)
        except validate.CapacityError:
            warnings.warn(
                "RPCAService.submit() returning None at capacity is "
                "deprecated; call try_submit() and handle CapacityError",
                DeprecationWarning,
                stacklevel=2,
            )
            return None

    def tick(self) -> None:
        """Advance every in-flight problem by ``rounds_per_tick`` rounds.

        Lanes tick sequentially; each advances only its own occupied slots
        (disjoint sets), so the shared per-slot counters compose.
        """
        methods = np.asarray(self._slot_method)
        for name, lane in self._lanes.items():
            lane_active = self._active & (methods == name)
            if not lane_active.any():  # host-side skip: no device sync
                continue
            (lane.carry, self._t, self._done, self._rounds,
             self._hit, self._dived) = lane._tick(
                lane.problems, lane.carry, self._t, self._done,
                self._rounds, self._hit, self._dived,
                jnp.asarray(lane_active),
            )

    def poll(self, slot: int) -> RPCAResponse | None:
        """Result for ``slot`` if it finished, else ``None``.  The slot stays
        occupied until :meth:`release` (its factors remain pollable)."""
        if not (0 <= slot < self.scfg.slots) or not self._active[slot]:
            raise ValueError(f"slot {slot} is not occupied")
        done = np.asarray(self._done)
        rounds = np.asarray(self._rounds)
        if not done[slot]:
            return None
        lane = self._lanes[self._slot_method[slot]]
        take = lambda tree: jax.tree.map(lambda a: a[slot], tree)
        fin = lane._finalize_one(take(lane.problems), take(lane.carry))
        l, s, u, v = lane.hooks.unpack(fin)
        n_req = int(self._slot_n[slot])
        if n_req < self.n:  # ragged submission: trim the padded tail
            l, s = l[:, :n_req], s[:, :n_req]
            if v is not None:
                v = v[:n_req]
        dived = bool(np.asarray(self._dived)[slot])
        if dived:
            # A quarantined tenant's calibration entry is suspect (the
            # same plane would diverge again): evict it now instead of
            # letting a warm refresh of poisoned data hit the cache.
            fp = self._slot_lam_fp[slot]
            self._slot_lam_fp[slot] = None
            if fp is not None:
                self._lam_cache.pop(fp, None)
        return RPCAResponse(
            l=l, s=s, u=u, v=v,
            rounds=int(rounds[slot]),
            converged=bool(np.asarray(self._hit)[slot]),
            method=lane.method,
            diverged=dived,
        )

    def release(self, slot: int) -> None:
        """Free ``slot`` for reuse and drop its per-slot bookkeeping.

        Also evicts the slot's fingerprint-keyed ``robust_lam``
        calibration-cache entry -- unless another occupied slot shares
        the same (M, mask) fingerprint -- so a long-lived service (or
        the gateway above it) never accumulates entries for departed
        tenants.  A tenant that later resubmits bit-identical data
        simply recalibrates once; the cache exists for *in-tenancy* warm
        refreshes, not as an unbounded tenant directory.
        """
        if not (0 <= slot < self.scfg.slots) or not self._active[slot]:
            raise ValueError(f"slot {slot} is not occupied")
        self._active[slot] = False
        fp = self._slot_lam_fp[slot]
        self._slot_lam_fp[slot] = None
        if fp is not None and not any(
            self._slot_lam_fp[i] == fp
            for i in np.flatnonzero(self._active)
        ):
            self._lam_cache.pop(fp, None)

    def pending(self) -> int:
        """Number of occupied slots still iterating."""
        return int((self._active & ~np.asarray(self._done)).sum())

    def metrics(self) -> dict[str, Any]:
        """Serving metrics: slot occupancy plus the shared compile-cache
        counters (process-wide -- every service and the front door share
        one cache), this service's lam-calibration cache counters, and
        the process-wide DCF consensus traffic counters (modelled bytes
        shipped per consensus round and the achieved compression ratio;
        see ``distributed.multihost.consensus_traffic``)."""
        from repro.distributed import multihost as mh

        cache = cc.default_cache()
        methods = np.asarray(self._slot_method)
        return {
            "slots": int(self.scfg.slots),
            "active": int(self._active.sum()),
            "pending": self.pending(),
            # occupied slots currently quarantined with a non-finite
            # residual (freed on release like any other finished slot).
            "diverged": int((self._active & np.asarray(self._dived)).sum()),
            # per-lane occupancy over the shared slot table; release()
            # decrements the owning lane's count.
            "lanes": {
                name: int((self._active & (methods == name)).sum())
                for name in self._lanes
            },
            "compile_cache": {
                **cache.stats.as_dict(),
                "entries": len(cache),
                "bytes": cache.nbytes,
            },
            "lam_cache": {
                "hits": self._lam_hits,
                "misses": self._lam_misses,
                "entries": len(self._lam_cache),
            },
            "consensus": mh.consensus_traffic(),
        }

    # -- convenience --------------------------------------------------------
    def solve_all(
        self,
        matrices: list[Array],
        warm: dict[int, tuple[Array, Array]] | None = None,
        masks: dict[int, Array] | None = None,
        methods: dict[int, str] | None = None,
    ) -> list[RPCAResponse]:
        """Drain a queue of problems through the slots (continuous refill).

        ``warm`` maps queue indices to prior factors, ``masks`` to
        observation masks, ``methods`` to per-request solver names.
        Returns responses in queue order.
        """
        warm = warm or {}
        masks = masks or {}
        methods = methods or {}
        results: list[RPCAResponse | None] = [None] * len(matrices)
        queue = list(enumerate(matrices))
        in_flight: dict[int, int] = {}  # slot -> queue index
        while queue or in_flight:
            while queue:
                qi, mat = queue[0]
                try:
                    slot = self.try_submit(mat, warm.get(qi),
                                           mask=masks.get(qi),
                                           method=methods.get(qi))
                except validate.CapacityError:
                    break
                queue.pop(0)
                in_flight[slot] = qi
            self.tick()
            for slot in list(in_flight):
                resp = self.poll(slot)
                if resp is not None:
                    results[in_flight.pop(slot)] = resp
                    self.release(slot)
        return results
