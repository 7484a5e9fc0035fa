"""chip_smoke.py refuses to run anywhere but on a TPU, and prints no result
when it refuses (its last stdout line is the result)."""
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script: str, **env_overrides: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.pop("RPCA_INTERPRET", None)
    env.update(env_overrides)
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, env=env,
                          cwd=os.path.dirname(script))


def test_cpu_backend_refused():
    out = _run(SCRIPT, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_interpret_env_refused():
    out = _run(SCRIPT, JAX_PLATFORMS="cpu", RPCA_INTERPRET="1")
    assert out.returncode == 2
    assert "RPCA_INTERPRET" in out.stderr
    assert '"ok"' not in out.stdout


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(str(lone), JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
