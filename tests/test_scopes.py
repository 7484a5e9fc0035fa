"""The solve's phases are named on the device, and a front-door ``dcf``
solve returns without waiting for the device.

``jax.named_scope`` names land in each compiled instruction's ``op_name``
metadata, which a profiler trace carries as the op's ``tf_op`` stat: the
benchmark (``bench/scopes.py``) attributes device time to
``rpca.calibrate``, ``rpca.round``, ``rpca.ridge`` and ``rpca.consensus``
from it.  These tests read the optimized HLO of each program on the CPU;
the kernels run through the Pallas interpreter so that they carry their
``jit(huber_contract_*)`` names as on the chip.
"""
import importlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import rpca
from repro.core import factorized as fz
from repro.core import runtime as rt
from repro.distributed import multihost as mh
from repro.serving.rpca_service import RPCAService, RPCAServiceConfig

cf_pca = importlib.import_module("repro.core.cf_pca")
dcf_pca = importlib.import_module("repro.core.dcf_pca")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M = N = 32
RANK = 3

_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _ops(hlo: str) -> list[tuple[str, str]]:
    """``(opcode, op_name)`` of every instruction that carries an
    ``op_name`` in an HLO module's text."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        name = _OP_NAME.search(line)
        if not m or not name:
            continue
        rest = m.group(1)
        if rest.startswith("("):  # tuple shape: skip to its closing paren
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    rest = rest[i + 1:]
                    break
        else:
            rest = rest.split(" ", 1)[1]
        out.append((rest.strip().split("(", 1)[0], name.group(1)))
    return out


def _problem():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    m_obs = jax.random.normal(k1, (M, N))
    mask = (jax.random.uniform(k2, (M, N)) < 0.8).astype(jnp.float32)
    return m_obs, mask


def _cf_programs(tmp_path):
    m_obs, _ = _problem()
    cfg = fz.DCFConfig.tuned(RANK, impl="pallas", outer_iters=3)
    return [cf_pca._solve.lower(m_obs, cfg, jax.random.PRNGKey(1),
                                run=rt.FIXED).compile().as_text()]


def _dcf_programs(tmp_path):
    m_obs, mask = _problem()
    cfg = fz.DCFConfig.masked(RANK, impl="pallas", outer_iters=3)
    return [dcf_pca._solve.lower(m_obs, cfg, 4, jax.random.PRNGKey(1),
                                 run=rt.FIXED, mask=mask)
            .compile().as_text()]


def _service_programs(tmp_path):
    svc = RPCAService(M, N, fz.DCFConfig.tuned(RANK, impl="pallas"),
                      RPCAServiceConfig(slots=2, rounds_per_tick=2))
    return [svc._lanes["cf"]._tick.as_text()]


def _sharded_programs(tmp_path):
    """Every program a masked ``dcf_sharded`` front-door solve compiles on
    four virtual CPU devices (XLA's dump: the calibration runs as its own
    program ahead of the shard_map solve)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        f"--xla_dump_to={tmp_path} --xla_dump_hlo_as_text")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh
        import numpy as np
        from repro import rpca
        from repro.core import factorized as fz
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        m_obs = jax.random.normal(k1, ({M}, {N}))
        mask = (jax.random.uniform(k2, ({M}, {N})) < 0.8).astype(jnp.float32)
        mesh = Mesh(np.array(jax.devices()), ("data",))
        cfg = fz.DCFConfig.masked({RANK}, impl="pallas", outer_iters=3)
        res = rpca.solve(rpca.RPCASpec(m_obs, mask=mask, rank={RANK},
                                       mesh=mesh),
                         method="dcf_sharded", cfg=cfg)
        jax.block_until_ready(res.l)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return [p.read_text() for p in tmp_path.glob("*after_optimizations.txt")]


def _under(ops, pick, scope):
    """The picked ops: at least one, each under ``scope``."""
    picked = [name for opcode, name in ops if pick(opcode, name)]
    assert picked, f"no op picked for {scope}"
    outside = [n for n in picked if scope not in n.split("/")]
    assert not outside, f"{len(outside)} ops outside {scope}: {outside[:3]}"


def _last(name: str) -> str:
    return name.rsplit("/", 1)[-1]


CHECKS = {
    # the medians' selection keys floats by their bits; so does the RNG
    "calibrate": lambda ops: _under(
        ops, lambda opc, n: (_last(n) == "bitcast_convert_type"
                             and "jit(_uniform)" not in n),
        "rpca.calibrate"),
    "round": lambda ops: _under(
        ops, lambda opc, n: "jit(huber_contract" in n, "rpca.round"),
    "ridge": lambda ops: (
        _under(ops, lambda opc, n: _last(n) in ("cholesky",
                                                "triangular_solve"),
               "rpca.ridge"),
        _under(ops, lambda opc, n: "rpca.ridge" in n, "rpca.round")),
    # under the client map too, ``rpca.consensus`` is a component of its
    # own (not ``vmap(rpca.consensus)``), as the trace readers match it
    "client mean": lambda ops: (
        _under(ops, lambda opc, n: opc == "reduce" and "rpca.consensus" in n,
               "rpca.round"),
        _under(ops, lambda opc, n: opc == "reduce" and "rpca.consensus" in n,
               "rpca.consensus")),
    "psum": lambda ops: _under(
        ops, lambda opc, n: opc.startswith("all-reduce"), "rpca.consensus"),
}

CASES = {
    "cf": (_cf_programs, ("calibrate", "round", "ridge")),
    "dcf": (_dcf_programs, ("calibrate", "round", "ridge", "client mean")),
    "dcf_sharded": (_sharded_programs,
                    ("calibrate", "round", "ridge", "psum")),
    "service_tick": (_service_programs, ("round", "ridge")),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_scopes_reach_the_compiled_programs(case, tmp_path):
    programs, checks = CASES[case]
    ops = [op for text in programs(tmp_path) for op in _ops(text)]
    assert ops
    for check in checks:
        CHECKS[check](ops)
    # The round and the calibration are disjoint top-level phases.
    both = [n for _, n in ops
            if "rpca.round" in n and "rpca.calibrate" in n]
    assert not both, both[:3]


def test_dcf_solve_returns_before_the_device_finishes():
    """The consensus traffic counter keeps the round count on the device:
    the front door neither copies it to the host nor waits for the solve,
    and the counter still reads the exact rounds once asked."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    m_obs = jax.random.normal(k1, (256, 256))
    mask = (jax.random.uniform(k2, (256, 256)) < 0.8).astype(jnp.float32)
    spec = rpca.RPCASpec(m_obs, mask=mask, rank=4, num_clients=4)
    jax.block_until_ready(rpca.solve(spec, method="dcf").l)  # compile
    mh.consensus_traffic(reset=True)
    with jax.transfer_guard_device_to_host("disallow"):
        res = rpca.solve(spec, method="dcf")
        # Hundreds of milliseconds of solve on the CPU are still running.
        assert not res.l.is_ready()
    jax.block_until_ready(res.l)
    traffic = mh.consensus_traffic()
    rounds = fz.DCFConfig.masked(4).outer_iters
    assert traffic["solves"] == 1
    assert traffic["rounds"] == int(res.stats.rounds) == rounds
    model = mh.consensus_wire_model(256, 4, 4)
    assert traffic["shipped_bytes"] == model["shipped_bytes"] * rounds
