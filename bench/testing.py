"""A copy of the benchmark at sizes a CPU test run can hold.

``tiny_root(dst)`` copies ``BENCHMARK.json`` and ``bench/`` into ``dst``
and shrinks every configuration, so that the CPU tests drive the same
harness, drivers and readers as a chip run, found by the same names.

It also adds, as files and entries of the copy alone, a four-device
``dcf_sharded`` cell (``dcf-sharded-4x8192``), so that the tests drive the
solve loop's mesh path on four CPU devices.  The benchmark itself has no
four-chip cell yet (``PERF.md``, Open questions).
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import harness
from bench.harness import BENCH, ROOT

SHARDED_CELL = "dcf-sharded-4x8192"
SHARDED_CONFIG = {
    "name": "synth-4chip", "m": 128, "n": 256, "rank": 4, "sparsity": 0.05,
    "chips": 4, "precision": "float32",
}
SHARDED_TRAFFIC = {
    "driver": "solve_loop", "method": "dcf_sharded", "clients": 4,
    "observed": 1.0,
    "algorithm": {"preset": "DCFConfig.tuned", "rounds": 100,
                  "local_iters": 2, "inner_sweeps": 3, "rho": 0.01,
                  "eta": 0.5, "lam_decay": 0.9, "lam_min_frac": 0.001},
    "limits": {"lowrank_err": 8e-4, "sparse_err": 1.1e-3,
               "factor_err": 8e-4, "ref_gap": 2e-5},
}

TINY = {"synth-8192": {"m": 128, "n": 128, "rank": 4}}


def run_on_cpu(root: Path, cell: str, seed: int, seconds: float,
               trace: bool = False) -> dict:
    """One harness run on the CPU: the look for a chip and the compile
    cache are stood in for here, in the tests' own code."""
    import jax

    saved = harness.require_devices, harness.enable_compile_cache
    harness.require_devices = lambda chips: jax.devices()[:chips]
    harness.enable_compile_cache = lambda root: None
    try:
        return harness.run_cell(root, cell, seed, seconds, trace)
    finally:
        harness.require_devices, harness.enable_compile_cache = saved


def tiny_root(dst: Path, src: Path = ROOT) -> Path:
    dst = Path(dst)
    shutil.copy(src / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(src / BENCH, dst / BENCH, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("out", "tests",
                                                  "__pycache__"))
    for name, sizes in TINY.items():
        path = dst / BENCH / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(sizes)
        path.write_text(json.dumps(cfg))
    traffic = dst / BENCH / "workloads" / "solve-dcf16-obs80.json"
    t = json.loads(traffic.read_text())
    t["clients"] = 4
    traffic.write_text(json.dumps(t))
    (dst / BENCH / "configs" / "synth-4chip.json").write_text(
        json.dumps(SHARDED_CONFIG))
    (dst / BENCH / "workloads" / "solve-dcf-sharded.json").write_text(
        json.dumps(SHARDED_TRAFFIC))
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": SHARDED_CELL, "config": "synth-4chip",
        "traffic": "solve-dcf-sharded", "chips": 4,
        "why": "dcf_sharded over a four-device mesh (tests only)"})
    for m in bench["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append(SHARDED_CELL)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst
