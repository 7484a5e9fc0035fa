#!/usr/bin/env python3
"""The control: the plain reference in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--precision bf16]

Makes the cell's problem from each seed at the cell's own size, solves it
with ``bench/reference.py`` at ``--precision`` (``bf16``: one bf16 pass a
product, the precision below the configurations' f32; ``highest``: the
witness) from the key of the window's first solve, and reads the numbers
that decide ``correct`` against the cell's limits: the recovery errors
against the ground truth and ``ref_gap``, the distance of its ``L`` from
the reference's at full precision.  The control of a sharded cell runs on
one device, with the same blocks side by side.  One JSON line a seed.
The benchmark's own runs never run it.
"""
import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT]


def readings(cell, seed: int, precision: str) -> dict:
    from bench import problems, reference
    from bench.errors import recovery_errors

    c, t = cell.config, cell.traffic
    a = t["algorithm"]
    clients = t.get("clients") or 1
    kw = dict(m=c["m"], n=c["n"], rank=c["rank"], sparsity=c["sparsity"],
              magnitude=math.sqrt(c["m"] * c["n"]),
              observed=t.get("observed", 1.0),
              parts=clients if t["method"] == "dcf_sharded" else 1)
    m_obs, mask = problems.make(seed, **kw)

    def solve(prec):
        return reference.solve(
            m_obs, mask, problems.host_key(seed, 0), rank=c["rank"],
            clients=clients, rounds=a["rounds"],
            local_iters=a["local_iters"], inner_sweeps=a["inner_sweeps"],
            rho=a["rho"], eta=a["eta"], lam_decay=a["lam_decay"],
            lam_min_frac=a["lam_min_frac"], precision=prec,
            init=t["method"])

    l, s = solve(precision)
    if precision == "highest":
        gap = 0.0
    else:
        l_ref, s_ref = solve("highest")
        gap = recovery_errors(l, s, l_ref, s_ref)[0]
        del l_ref, s_ref
    del m_obs, mask
    l0, s0 = problems.make(seed, truth=True, **kw)
    low, sparse = recovery_errors(l, s, l0, s0)
    lim = t["limits"]
    checks = {"lowrank_err": (low, lim["lowrank_err"]),
              "sparse_err": (sparse, lim["sparse_err"]),
              "ref_gap": (gap, lim["ref_gap"])}
    return {"seed": seed, "precision": precision,
            "correct": all(v <= l for v, l in checks.values()),
            "checks": {k: {"value": v, "limit": l}
                       for k, (v, l) in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="bf16",
                    choices=("bf16", "highest"))
    args = ap.parse_args(argv)
    from bench.harness import ROOT as root
    from bench.harness import enable_compile_cache, load_cell

    enable_compile_cache(root)
    _, cell = load_cell(root, args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = readings(cell, seed, args.precision)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
