#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_scopes.py`` reads.

    python bench/tests/record_dcf_small.py --out <path>.xplane.pb

On one TPU chip: a masked ``dcf`` solve through ``rpca.solve`` at
1024 x 1024, rank 16, 4 simulated clients, 80% observed, 12 rounds of the
masked preset, warmed up once and then traced inside a ``bench.window``
span, as ``bench/harness.py`` traces a window.  All four program scopes
(``rpca.calibrate``, ``rpca.round``, ``rpca.ridge``, ``rpca.consensus``)
run in it.  Prints the solve's rounds as a JSON line.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

SIZE, RANK, CLIENTS, ROUNDS = 1024, 16, 4, 12


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from bench import spans as spans_mod
    from repro import rpca
    from repro.core.factorized import DCFConfig

    if jax.devices()[0].platform != "tpu":
        print("no TPU: refusing to record", file=sys.stderr)
        return 3
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    m_obs = jax.random.normal(k1, (SIZE, SIZE))
    mask = (jax.random.uniform(k2, (SIZE, SIZE)) < 0.8).astype(jnp.float32)
    spec = rpca.RPCASpec(m_obs, mask=mask, rank=RANK, num_clients=CLIENTS)
    cfg = DCFConfig.masked(RANK, outer_iters=ROUNDS)

    def solve():
        res = rpca.solve(spec, method="dcf", cfg=cfg)
        jax.block_until_ready((res.l, res.s))
        return res

    solve()  # compile outside the trace
    spans = spans_mod.Spans()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with spans("window"):
            res = solve()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(found[0], args.out)
    print(json.dumps({"rounds": [int(res.stats.rounds)],
                      "bytes": os.path.getsize(args.out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
