#!/usr/bin/env python3
"""On-chip benchmark of the distributed RPCA solvers.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``).  One run is one process: it makes the
cell's inputs on the device from ``--seed``, warms up every shape the cell
uses (set-up, reported as ``setup_s``), measures for ``--seconds``, checks
sampled results against the generated ground truth and against the plain
reference (``reference.py``), and prints one JSON object as the last line
of standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {"platform", "kind", "count", "memory_peak_bytes", ...},
     "breakdown": {...},           # --trace 1 only
     "checks": {name: {"value", "limit"}}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window, with the device's
busy and window seconds and a breakdown of device time and idle gaps.  The
numbers compared for ``correct`` are also the last lines of standard
error.  A run exits nonzero and prints no result when JAX finds no TPU or
fewer chips than the cell asks for; it never falls back to the CPU.

Layout (``bench/``): ``configs/`` the deployments, ``workloads/`` the
traffic mixes, ``drivers/`` one module per kind of traffic,
``layer_metrics/`` one reader per per-layer metric, ``tests/`` the CPU
tests.  Shared pieces: ``problems.py`` (generator), ``work.py``
(operations and bytes of the contraction layer), ``peaks.py`` (chip
peaks), ``trace_events.py`` (trace reduction), ``errors.py`` (error
arithmetic), ``reference.py`` (plain DCF-PCA, the control),
``spans.py`` (host spans, compile clock).  ``control.py`` runs the
control on the chip.
Traces and run outputs go to ``bench/out/``; the compile cache to
``.jax_cache/`` at the root of the checkout.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The script's own directory is not a package root: import as ``bench.*``.
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(t_start=T_START))
