"""A new configuration, cell and per-layer metric are files and entries
alone."""
import hashlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, testing  # noqa: E402

READER = '''"""Solves completed in the window (a metric added as a file)."""


def read(ctx):
    return ctx.counters.get("solves")
'''


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_workload_and_metric_need_no_edit(tmp_path):
    root = testing.tiny_root(tmp_path)
    before = _digests(root)
    # A new configuration and a new traffic mix: data files that the
    # harness and the general solve driver read.
    config = json.loads((root / "bench/configs/synth-8192.json").read_text())
    config["name"] = "synth-new"
    (root / "bench/configs/synth-new.json").write_text(json.dumps(config))
    (root / "bench/workloads/solve-cf-new.json").write_text(
        (root / "bench/workloads/solve-cf.json").read_text())
    (root / "bench/layer_metrics/solves_in_window.py").write_text(READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "cf-new", "config": "synth-new",
        "traffic": "solve-cf-new", "chips": 1,
        "why": "a cell added by files and entries alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("cf-new")
    bench["per_layer"].append({
        "name": "solves_in_window", "unit": "solves", "better": "higher",
        "source": "host_clock", "layer": "front door", "moves": "solve_s",
        "workloads": ["cf-new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    e2e = testing.run_on_cpu(root, "cf-new", 7, 0.5)
    assert e2e["correct"] and set(e2e["metrics"]) == {"setup_s", "solve_s"}
    traced = testing.run_on_cpu(root, "cf-new", 7, 0.5, trace=True)
    assert traced["correct"]
    assert traced["metrics"]["solves_in_window"]["value"] >= 1
    # The older cells do not report the new metric.
    _, layer = harness.cell_metrics(bench, "cf-8192")
    assert "solves_in_window" not in {m["name"] for m in layer}
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
