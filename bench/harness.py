"""The benchmark harness: finds a cell's files by name and runs it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``     the deployment: sizes, source, cuts;
* ``bench/workloads/<traffic>.json``  the traffic: its driver and
                                      parameters, and the limits of the
                                      comparison that decides ``correct``;
* ``bench/drivers/<driver>.py``       one module per kind of traffic;
* ``bench/layer_metrics/<metric>.py`` one reader per per-layer metric.

A driver module provides ``setup(cell, seed, spans, devices)``,
``window(state, seconds, spans)``, ``end_to_end(counters)``,
``release(state)`` and ``check(state)``; a reader provides ``read(ctx)``,
which returns a number or ``None`` when it finds nothing to read.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any

ROOT = Path(__file__).resolve().parents[1]
BENCH = "bench"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: dict
    root: Path

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


@dataclass
class Context:
    """What a per-layer reader sees of one traced run."""

    cell: Cell
    counters: dict
    trace: Any  # trace_events.Trace, or None
    device_kind: str


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file by its path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, name: str) -> tuple[dict, Cell]:
    bench = read_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{', '.join(sorted(entries))}")
    w = entries[name]
    config = read_json(root / BENCH / "configs" / f"{w['config']}.json")
    traffic = read_json(root / BENCH / "workloads" / f"{w['traffic']}.json")
    return bench, Cell(name=name, chips=int(w["chips"]), config=config,
                       traffic=traffic, entry=w, root=root)


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics that ``cell`` reports: those
    that list it under ``workloads``, or have no such list (a per-layer
    metric without one goes wherever the metric it moves is reported)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def require_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def finite(v):
    """JSON has no NaN or infinity: those print as ``null``."""
    return float(v) if v is not None and math.isfinite(v) else None


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float | None = None) -> dict:
    """One run of one cell; returns the result object."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, cell = load_cell(root, workload)
    e2e_entries, layer_entries = cell_metrics(bench, cell.name)
    import jax

    from bench import spans as spans_mod

    devices = require_devices(cell.chips)
    enable_compile_cache(root)
    driver = load_module(root / BENCH / "drivers" / f"{cell.driver}.py",
                         f"bench_driver_{cell.driver}")
    spans = spans_mod.Spans()
    clock = spans_mod.compile_clock()
    state = driver.setup(cell, seed, spans, devices)
    setup_s = time.perf_counter() - t_start

    trace_dir = root / BENCH / "out" / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    c0 = clock.seconds
    try:
        with spans("window"):
            counters = driver.window(state, seconds, spans)
    finally:
        if trace:
            jax.profiler.stop_trace()
    counters["compile_s"] = clock.seconds - c0
    mem = memory_peak(devices)
    driver.release(state)
    with spans("check"):
        checks = driver.check(state)
    del state

    from bench.errors import all_ok

    correct = all_ok(checks)
    metrics: dict[str, dict] = {}
    result: dict[str, Any] = {}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if not trace:
        values = driver.end_to_end(counters)
        values["setup_s"] = setup_s
        for m in e2e_entries:
            if m["name"] in values and values[m["name"]] is not None:
                metrics[m["name"]] = {"value": finite(values[m["name"]]),
                                      "unit": m["unit"]}
    else:
        from bench import trace_events as te

        tr = te.load(str(trace_dir))
        ctx = Context(cell=cell, counters=counters, trace=tr,
                      device_kind=dev.device_kind)
        for m in layer_entries:
            reader = load_module(
                root / BENCH / "layer_metrics" / f"{m['name']}.py",
                "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": finite(v), "unit": m["unit"]}
        win = tr.window()
        if win is not None and tr.devices:
            device["busy_s"] = te.mean_over_devices(tr, te.busy_s)
            device["window_s"] = win[1] - win[0]
            ops0 = next(iter(tr.devices.values()))
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in te.top_ops(ops0, *win)],
                "idle_gaps": [[n, s] for n, s in
                              te.labelled_gaps(ops0, tr.spans, *win)],
            }
    attempted = int(counters.get("attempted", 0))
    failed = int(counters.get("failed", 0))
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {c.name: {"value": finite(c.value), "limit": c.limit}
                     for c in checks}
    return out


def main(argv=None, *, root: Path = ROOT,
         t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
