"""Benchmark driver: one module per paper table/figure + framework benches.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig1,fig4]

Prints ``name,us_per_call,derived`` CSV lines per bench.  Exits nonzero
when any bench raised (after running the rest).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import (  # noqa: E402
    aot_dispatch_bench,
    api_dispatch_bench,
    consensus_bench,
    elastic_bench,
    fault_tolerance_bench,
    fig1_convergence,
    fig2_phase,
    fig4_local_iters,
    fused_round_bench,
    gateway_bench,
    grad_compress_bench,
    kernel_micro,
    masked_rpca_bench,
    roofline_summary,
    solver_runtime_bench,
    table1_upper_rank,
)
from repro.jax_cache import enable_persistent_cache  # noqa: E402

BENCHES = {
    "fig1": fig1_convergence,
    "fig2": fig2_phase,
    "table1": table1_upper_rank,
    "fig4": fig4_local_iters,
    "kernel": kernel_micro,
    "fused": fused_round_bench,
    "masked": masked_rpca_bench,
    "elastic": elastic_bench,
    "fault": fault_tolerance_bench,
    "api": api_dispatch_bench,
    "aot": aot_dispatch_bench,
    "gateway": gateway_bench,
    "consensus": consensus_bench,
    "grad_compress": grad_compress_bench,
    "roofline": roofline_summary,
    "runtime": solver_runtime_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale problem sizes (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench subset")
    ap.add_argument("--json-out", default=os.path.join(HERE,
                                                       "bench_results.json"))
    args = ap.parse_args()
    enable_persistent_cache()

    names = list(BENCHES) if not args.only else args.only.split(",")
    all_rows = {}
    failed = []
    for name in names:
        print(f"# === {name} ===", flush=True)
        try:
            all_rows[name] = BENCHES[name].main(full=args.full)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{e!r}")
            all_rows[name] = {"error": repr(e)}
            failed.append(name)
    with open(args.json_out, "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    print(f"# wrote {args.json_out}")
    if failed:
        sys.exit(f"benches raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
