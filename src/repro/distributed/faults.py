"""Deterministic fault injection for the distributed stack (DESIGN.md
Sec. 17).

Chaos testing that is *reproducible by construction*: a :class:`FaultPlan`
is a seed-keyed ``(T, E)`` table of per-round, per-client fault codes,
materialized once on the host (``numpy`` RNG -- identical on every
process and every platform) and injected at the consensus boundary of
both DCF engines.  A chaos scenario is therefore an ordinary test case --
same seed, same faults, same bits -- never a flake.

Fault taxonomy (one code per client per round):

=========  ==============================================================
``OK``     no fault.
``CRASH``  the client dies mid-round: no payload reaches the consensus
           and its ``V_i`` freezes (it did no local work) -- exactly a
           participation dropout, but adversarially scheduled.
``NAN``    Byzantine payload: the client ships a NaN-filled factor.  A
           weighted mean is destroyed instantly; robust aggregators
           quarantine the vote (one-vote finiteness check).
``CORRUPT``  Byzantine payload: the factor arrives scaled by
           ``CORRUPT_SCALE`` (a gross-but-finite corruption -- the regime
           where ``trimmed_mean`` is the cheapest sufficient defense).
``STALE``  straggler: the client re-ships the previous consensus ``U``
           (a zero delta) while its local ``V_i`` keeps advancing.
``FLAKY``  flaky collective: the local round ran (``V_i`` advances) but
           the message is lost -- dropped from the consensus like a
           crash, without freezing local state.
=========  ==============================================================

Process-level faults (kill + respawn of a real worker) are driven by the
``multihost.launch_workers`` harness plus the checkpoint/resume machinery;
this module covers everything that happens *inside* a live process.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

OK = 0
CRASH = 1
NAN = 2
CORRUPT = 3
STALE = 4
FLAKY = 5

#: All recognized fault codes (kept dense so a code table round-trips
#: through int8 checkpoints without loss).
ALL_CODES = (OK, CRASH, NAN, CORRUPT, STALE, FLAKY)

#: Scale applied to a ``CORRUPT`` payload.  Gross (64x) but finite: big
#: enough that one corrupt client visibly wrecks a plain mean, bounded so
#: the trimmed-mean regime is exercised distinctly from NaN quarantine.
CORRUPT_SCALE = 64.0

_NAMES = {OK: "ok", CRASH: "crash", NAN: "nan", CORRUPT: "corrupt",
          STALE: "stale", FLAKY: "flaky"}
_BY_NAME = {v: k for k, v in _NAMES.items()}


@dataclass(frozen=True, eq=False)
class FaultPlan:
    """A deterministic per-round, per-client fault schedule.

    ``codes`` is the host-side ``(rounds, num_clients)`` int32 table;
    round ``t`` of a solve uses row ``t % rounds`` (warm resumes wrap,
    matching the participation-schedule convention).  Construct via the
    classmethods -- they are the seed-keyed, reproducible surface.
    """

    codes: np.ndarray
    seed: int = 0
    meta: str = field(default="", compare=False)

    def __post_init__(self):
        arr = np.asarray(self.codes, np.int32)
        if arr.ndim != 2:
            raise ValueError(
                f"fault plan codes must be (rounds, num_clients), got "
                f"shape {arr.shape}"
            )
        bad = set(np.unique(arr)) - set(ALL_CODES)
        if bad:
            raise ValueError(f"unknown fault codes in plan: {sorted(bad)}")
        object.__setattr__(self, "codes", arr)

    # -- constructors ------------------------------------------------------
    @classmethod
    def none(cls, rounds: int, num_clients: int) -> "FaultPlan":
        """The explicit no-fault plan (useful as a control arm)."""
        return cls(np.zeros((rounds, num_clients), np.int32), meta="none")

    @classmethod
    def byzantine(
        cls,
        rounds: int,
        num_clients: int,
        clients: Sequence[int],
        kind: str = "nan",
        start: int = 0,
    ) -> "FaultPlan":
        """``len(clients)`` permanently-Byzantine clients from round
        ``start`` on: every scheduled round they ship a ``kind`` payload
        (``"nan"``, ``"corrupt"``, ``"stale"``) or drop (``"crash"``,
        ``"flaky"``)."""
        code = _BY_NAME.get(kind)
        if code is None or code == OK:
            raise ValueError(
                f"kind must be one of {sorted(_BY_NAME)} (not 'ok'), "
                f"got {kind!r}"
            )
        table = np.zeros((rounds, num_clients), np.int32)
        for i in clients:
            if not 0 <= int(i) < num_clients:
                raise ValueError(
                    f"client index {i} out of range for "
                    f"num_clients={num_clients}"
                )
            table[start:, int(i)] = code
        return cls(table, meta=f"byzantine:{kind}x{len(list(clients))}")

    @classmethod
    def random(
        cls,
        seed: int,
        rounds: int,
        num_clients: int,
        rates: Mapping[str, float],
    ) -> "FaultPlan":
        """Seed-keyed i.i.d. chaos: each (round, client) cell draws one
        fault from ``rates`` (name -> probability; the remainder is OK).
        At most ``num_clients - 1`` clients are faulted per round, so a
        consensus always has at least one live vote."""
        kinds = sorted(rates)
        p = [float(rates[k]) for k in kinds]
        if any(not 0.0 <= x <= 1.0 for x in p) or sum(p) > 1.0:
            raise ValueError(
                f"fault rates must be probabilities summing to <= 1, "
                f"got {rates!r}"
            )
        rng = np.random.default_rng(seed)
        draw = rng.choice(
            len(kinds) + 1, size=(rounds, num_clients),
            p=p + [1.0 - sum(p)],
        )
        table = np.zeros((rounds, num_clients), np.int32)
        for j, k in enumerate(kinds):
            table[draw == j] = _BY_NAME[k]
        for t in range(rounds):  # keep one live vote per round
            faulted = np.flatnonzero(table[t])
            if faulted.size >= num_clients:
                spare = rng.integers(num_clients)
                table[t, spare] = OK
        return cls(table, seed=seed, meta=f"random:{dict(rates)}")

    # -- views -------------------------------------------------------------
    @property
    def rounds(self) -> int:
        return self.codes.shape[0]

    @property
    def num_clients(self) -> int:
        return self.codes.shape[1]

    def table(self) -> Array:
        """The device-side code table -- what rides the problem pytree."""
        return jnp.asarray(self.codes, jnp.int32)

    def describe(self) -> str:
        counts = {name: int((self.codes == code).sum())
                  for code, name in _NAMES.items() if code != OK}
        busy = {k: v for k, v in counts.items() if v}
        return (f"FaultPlan(seed={self.seed}, rounds={self.rounds}, "
                f"clients={self.num_clients}, faults={busy or 'none'})")


def resolve_faults(faults) -> Array | None:
    """Normalize a ``faults=`` argument (plan, table, or None) into the
    device-side int32 code table."""
    if faults is None:
        return None
    if isinstance(faults, FaultPlan):
        return faults.table()
    return jnp.asarray(faults, jnp.int32)


# ---------------------------------------------------------------------------
# Traced injection at the consensus boundary
# ---------------------------------------------------------------------------
def corrupt_payload(code: Array, u_i: Array, u_prev: Array) -> Array:
    """Apply this client's payload fault (its scalar ``code``) to what it
    ships.  ``CRASH``/``FLAKY`` leave the payload untouched -- their
    effect is a dropped *vote*, applied through :func:`live_mask`.
    """
    u = jnp.where(code == NAN, jnp.float32(jnp.nan), u_i)
    u = jnp.where(code == CORRUPT, CORRUPT_SCALE * u_i, u)
    return jnp.where(code == STALE, u_prev, u)


def live_mask(code: Array) -> Array:
    """1.0 where the client's payload reaches the consensus this round
    (folds into the participation weight)."""
    return ((code != CRASH) & (code != FLAKY)).astype(jnp.float32)


def v_advance_mask(code: Array) -> Array:
    """1.0 where the client's local ``V_i`` advances this round: every
    fault except a crash ran the local computation."""
    return (code != CRASH).astype(jnp.float32)
