"""A run whose timed path is broken underneath reads ``correct: false``.

Each fault is planted in the program for one run of the harness, which
then goes through set-up, window and check as on the chip (the look for
a chip skipped, at a size a CPU test run can hold):

* a step that returns its state unchanged;
* half of the clients left out of the consensus, the mean taken over the
  rest (the cells with more than one client);
* the exchange between chips left out: each chip keeps its own factor
  (only the four-device cell has such an exchange);
* the answer altered where it is produced (``L`` off by one part in a
  hundred).

The two consensus faults leave every error against the ground truth
within its limit: any half of the silos, or each silo alone from the
shared start, holds enough columns to fix the rank-r column space.  What
refuses them is ``ref_gap``, the distance from the plain reference run
from the same start: the consensus of fewer clients converges to another
point, about a thousand times farther from the reference than a sound
solve.

The four-device cell's faults run in a subprocess with four CPU devices.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve()
sys.path.insert(0, str(HERE.parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from bench import testing  # noqa: E402

SEED = 2**31 + 9


def _unchanged(orig):
    def local_round(u_global, v, m_blk, **kw):
        z = jnp.zeros((), jnp.float32)
        return u_global, v, (z, z)
    return local_round


def _half_clients(orig):
    def aggregate_stacked(cfg, u_i, u_prev, **kw):
        return jnp.mean(u_i[: u_i.shape[0] // 2], axis=0), None
    return aggregate_stacked


def _altered(orig):
    def finalize(u, v, m_blk, lam, impl, w=None):
        l, s = orig(u, v, m_blk, lam, impl, w=w)
        return l * 1.01, s
    return finalize


def _sharded_half(orig):
    def aggregate_sharded(cfg, u_i, u_prev, *, axes, **kw):
        keep = (jax.lax.axis_index(axes) % 2 == 0).astype(u_i.dtype)
        return jax.lax.psum(u_i * keep, axes) / jax.lax.psum(keep, axes), None
    return aggregate_sharded


def _sharded_no_exchange(orig):
    def aggregate_sharded(cfg, u_i, u_prev, *, axes, **kw):
        return u_i, None
    return aggregate_sharded


FAULTS = {
    "unchanged": ("local_round", _unchanged),
    "half_clients": ("aggregate_stacked", _half_clients),
    "altered": ("finalize", _altered),
    "sharded_half": ("aggregate_sharded", _sharded_half),
    "sharded_no_exchange": ("aggregate_sharded", _sharded_no_exchange),
}


def _clear():
    from repro.core.compile_cache import default_cache

    jax.clear_caches()
    default_cache().clear()


def run_with_fault(root: Path, cell: str, fault: str | None) -> dict:
    from repro.core import factorized as fz

    name, make = FAULTS[fault] if fault else (None, None)
    orig = getattr(fz, name) if name else None
    _clear()
    try:
        if name:
            setattr(fz, name, make(orig))
        return testing.run_on_cpu(root, cell, SEED, 0.2)
    finally:
        if name:
            setattr(fz, name, orig)
        _clear()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,fault", [
    ("cf-8192", None), ("cf-8192", "unchanged"), ("cf-8192", "altered"),
    ("dcf16-8192-obs80", None), ("dcf16-8192-obs80", "unchanged"),
    ("dcf16-8192-obs80", "altered"),
])
def test_fault_reads_not_correct(root, cell, fault):
    out = run_with_fault(root, cell, fault)
    assert out["correct"] is (fault is None), out["checks"]


def test_half_of_the_clients_is_seen_only_by_the_reference(root):
    out = run_with_fault(root, "dcf16-8192-obs80", "half_clients")
    assert out["correct"] is False
    checks = {k: v for k, v in out["checks"].items() if k != "ref_gap"}
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    gap = out["checks"]["ref_gap"]
    assert gap["value"] > 3 * gap["limit"], gap


def test_sharded_faults_read_not_correct(tmp_path):
    """The four-device cell on four CPU devices, in a process of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE.parents[2]), str(HERE.parents[2] / "src")])
    code = (
        "import json, sys\n"
        "from pathlib import Path\n"
        f"sys.path.insert(0, {str(HERE.parent)!r})\n"
        "import test_bench_faults as t\n"
        "from bench import testing\n"
        f"root = testing.tiny_root(Path({str(tmp_path)!r}))\n"
        "for f in (None, 'unchanged', 'altered', 'sharded_no_exchange',"
        " 'sharded_half'):\n"
        "    out = t.run_with_fault(root, 'dcf-sharded-4x8192', f)\n"
        "    print(json.dumps({'fault': f, 'correct': out['correct']}))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = [json.loads(x) for x in proc.stdout.splitlines()
           if x.startswith("{")]
    assert [(g["fault"], g["correct"]) for g in got] == [
        (None, True), ("unchanged", False), ("altered", False),
        ("sharded_no_exchange", False), ("sharded_half", False)]
