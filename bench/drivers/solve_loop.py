"""Closed loop of back-to-back solves through ``repro.rpca.solve``.

One problem is made on the device from the seed at set-up; every solve
of the window solves it again from its own random start (its own key),
through the front door's default preset for the method.  Each solve ends
with ``block_until_ready`` on ``L`` and ``S`` before the next starts, and
the window closes at the end of the first solve that ends past
``--seconds``.  ``solve_s`` is the window's length over its solves.

Two of the window's solves, drawn from the seed, are checked once the
window has closed: their ``L``, ``S`` and ``U V^T`` against the ground
truth that the generator remakes from the seed, and their ``L`` against
``bench/reference.py`` run at full f32 precision on the same inputs from
the same key (``ref_gap``).  The ground truth holds each solve to the
recovery error the configuration states; the reference holds it to the
algorithm, which is what sees a consensus that leaves clients out.  Every
solve must have run its preset's rounds.

Traffic parameters (``bench/workloads/<traffic>.json``):

``method``     ``cf``, ``dcf`` or ``dcf_sharded``;
``clients``    simulated clients of ``dcf`` (``dcf_sharded``: one a chip);
``observed``   density of the uniform observation mask (1 = none);
``algorithm``  the preset's stated parameters: ``rounds``,
               ``local_iters``, ``inner_sweeps``, ``rho``, ``eta``,
               ``lam_decay``, ``lam_min_frac`` (for the work count, the
               reference and the control);
``limits``     the limit of each number compared.
"""
from __future__ import annotations

import math
import random
import time

import jax
import numpy as np

from bench import problems, reference
from bench.errors import Check, factor_error, recovery_errors

#: Solves of a window checked after it closes.
SAMPLE = 2
#: The mesh axis a ``dcf_sharded`` cell splits the columns over.
AXIS = "data"


class State:
    pass


def _problem_kw(cell) -> dict:
    c, t = cell.config, cell.traffic
    return dict(m=c["m"], n=c["n"], rank=c["rank"], sparsity=c["sparsity"],
                magnitude=math.sqrt(c["m"] * c["n"]),
                observed=t.get("observed", 1.0))


def _mesh(cell, devices):
    if cell.traffic["method"] != "dcf_sharded":
        return None
    from jax.sharding import Mesh

    return Mesh(np.array(devices), (AXIS,))


def setup(cell, seed: int, spans, devices) -> State:
    from repro import rpca

    st = State()
    st.cell, st.seed, st.rpca = cell, seed, rpca
    st.method = cell.traffic["method"]
    st.mesh = _mesh(cell, devices)
    with spans("generate"):
        st.m_obs, st.mask = problems.make(seed, mesh=st.mesh,
                                          **_problem_kw(cell))
        jax.block_until_ready((st.m_obs, st.mask))
    st.num_clients = (cell.traffic["clients"] if st.method == "dcf"
                      else None)
    st.rng = random.Random(seed)
    st.kept: list = []
    st.rounds: list[int] = []
    # Warm-up: the solve's own programs, at the window's shapes.
    with spans("solve"):
        _solve(st, problems.host_key(seed, -1))
    return st


def _solve(st: State, key):
    res = st.rpca.solve(
        st.rpca.RPCASpec(st.m_obs, mask=st.mask, rank=st.cell.config["rank"],
                         num_clients=st.num_clients, mesh=st.mesh, key=key),
        method=st.method)
    jax.block_until_ready((res.l, res.s))
    return res


def window(st: State, seconds: float, spans) -> dict:
    t0 = time.perf_counter()
    i = 0
    while True:
        with spans("solve"):
            res = _solve(st, problems.host_key(st.seed, i))
        t = time.perf_counter()
        st.rounds.append(int(res.stats.rounds))
        # Reservoir sample of the window's solves, drawn from the seed.
        answer = (i, res.l, res.s, res.u, res.v)
        if len(st.kept) < SAMPLE:
            st.kept.append(answer)
        else:
            j = st.rng.randrange(i + 1)
            if j < SAMPLE:
                st.kept[j] = answer
        del res
        i += 1
        if t - t0 >= seconds:
            break
    return {"solves": i, "window_s": t - t0, "rounds": list(st.rounds),
            "attempted": i, "failed": 0}


def end_to_end(counters: dict) -> dict:
    return {"solve_s": counters["window_s"] / counters["solves"]}


def release(st: State) -> None:
    """Drop the inputs; keep the sampled results for the check."""
    st.m_obs = st.mask = None


def _reference(st: State, m_obs, mask, index: int):
    a = st.cell.traffic["algorithm"]
    return reference.solve(
        m_obs, mask, problems.host_key(st.seed, index),
        rank=st.cell.config["rank"], clients=st.cell.traffic.get("clients")
        or 1, rounds=a["rounds"], local_iters=a["local_iters"],
        inner_sweeps=a["inner_sweeps"], rho=a["rho"], eta=a["eta"],
        lam_decay=a["lam_decay"], lam_min_frac=a["lam_min_frac"],
        precision="highest", init=st.method)


def check(st: State) -> list[Check]:
    lim = st.cell.traffic["limits"]
    kw = _problem_kw(st.cell)
    m_obs, mask = problems.make(st.seed, mesh=st.mesh, **kw)
    l0, s0 = problems.make(st.seed, mesh=st.mesh, truth=True, **kw)
    low = sparse = factor = gap = 0.0
    for index, l, s, u, v in st.kept:
        a, b = recovery_errors(l, s, l0, s0)
        low, sparse = max(low, a, key=_nan_max), max(sparse, b, key=_nan_max)
        factor = max(factor, factor_error(u, v, l0), key=_nan_max)
        l_ref, s_ref = _reference(st, m_obs, mask, index)
        gap = max(gap, recovery_errors(l, s, l_ref, s_ref)[0], key=_nan_max)
        del l_ref, s_ref
    st.kept = []
    want = st.cell.traffic["algorithm"]["rounds"]
    short = max((abs(want - r) for r in st.rounds), default=math.inf)
    return [Check("lowrank_err", low, lim["lowrank_err"]),
            Check("sparse_err", sparse, lim["sparse_err"]),
            Check("factor_err", factor, lim["factor_err"]),
            Check("ref_gap", gap, lim["ref_gap"]),
            Check("rounds_off", float(short), 0.0)]


def _nan_max(x: float) -> float:
    return math.inf if math.isnan(x) else x
