"""Consensus wire traffic: modelled + measured bytes per round, weak
scaling E = 4 -> 64 (ISSUE 7 acceptance: >= 4x measured consensus
bytes/round reduction at matched recovery error).

Four experiment families, all on the DCF consensus wire of DESIGN.md
Sec. 14:

``model_e{E}``    Modelled per-client consensus bytes per round from
                  ``multihost.consensus_wire_model`` under a *constant
                  total gather volume* policy ``topk_frac = 0.1 / E``
                  (k E = 0.1 d): as the federation grows the per-client
                  budget shrinks so the gathered wire stays ~10x under
                  the dense factor exchange at every E.  Deterministic
                  byte arithmetic -- the tight trajectory rows.

``wire_e4``       The measured anchor: the sharded engine's dense
                  all-reduce vs compressed all-gather collective bytes,
                  counted from the *compiled HLO* (result bytes x while
                  trip counts, ``roofline.hlo_costs``) on a 4-device
                  mesh in a subprocess.  topk_frac = 0.025 is the E = 4
                  point of the weak-scaling policy.

``quality_e4``    Recovery-error parity: dense vs top-k (k/d = 0.1)
                  consensus on the paper's synthetic setting at a
                  converged budget; the guard pins err_compressed <=
                  2x err_dense (the acceptance bound).

``weak_scaling``  Wall-clock view: simulated-client solves with a fixed
                  per-client column count (n = n_i E), E = 4 -> 64.
                  ``per_client_eff`` is (wall_4 / 4) / (wall_E / E) --
                  ~1 when the per-client cost stays flat as E grows.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax

from repro.core import (
    DCFConfig,
    dcf_pca,
    generate_problem,
    relative_error,
)
from repro.core.factorized import topk_k
from repro.distributed.grad_compress import CompressConfig
from repro.distributed.multihost import consensus_wire_model

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Weak-scaling wire policy: constant total gather volume k E = BUDGET d.
BUDGET = 0.1
ANCHOR_M, ANCHOR_RANK, ANCHOR_E = 256, 8, 4
ANCHOR_FRAC = BUDGET / ANCHOR_E  # 0.025
ANCHOR_ROUNDS = 20

_HLO_SNIPPET = """
import importlib, json
import jax, jax.numpy as jnp
from repro.launch.mesh import make_compat_mesh
from repro.core.factorized import DCFConfig
from repro.distributed.grad_compress import CompressConfig
from repro.roofline.hlo_costs import analyze_hlo

dcf = importlib.import_module("repro.core.dcf_pca")
m_obs = jax.random.normal(jax.random.PRNGKey(0), ({m}, {n}))
mesh = make_compat_mesh(({e},), ("data",))
out = {{}}
for tag, cc in (("dense", None),
                ("compressed", CompressConfig(topk_frac={frac}))):
    cfg = DCFConfig.tuned({rank}, outer_iters={rounds},
                          consensus_compress=cc)
    hlo = dcf.sharded_solve_hlo(m_obs, cfg, mesh,
                                key=jax.random.PRNGKey(1))
    out[tag] = dict(analyze_hlo(hlo).collective)
print("HLOJSON " + json.dumps(out))
"""


def _measured_anchor() -> dict:
    """Compile the sharded solve on a 4-device mesh (subprocess: jax pins
    the device count at first init) and count collective bytes from HLO."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ANCHOR_E}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = _HLO_SNIPPET.format(m=ANCHOR_M, n=ANCHOR_E * 64, e=ANCHOR_E,
                               rank=ANCHOR_RANK, frac=ANCHOR_FRAC,
                               rounds=ANCHOR_ROUNDS)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(f"HLO anchor failed:\n{out.stderr}")
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("HLOJSON "))
    coll = json.loads(line[len("HLOJSON "):])
    dense = sum(coll["dense"].values())
    comp = sum(coll["compressed"].values())
    d = ANCHOR_M * ANCHOR_RANK
    k = topk_k(d, ANCHOR_FRAC)
    row = {
        "bench": "consensus",
        "name": "wire_e4",
        "clients": ANCHOR_E,
        "dense_bytes_client_round": dense / ANCHOR_ROUNDS,
        "compressed_bytes_client_round": comp / ANCHOR_ROUNDS,
        "measured_ratio": dense / comp,
        "model_ratio": consensus_wire_model(
            ANCHOR_M, ANCHOR_RANK, ANCHOR_E,
            CompressConfig(topk_frac=ANCHOR_FRAC))["ratio"],
        "k": k,
        "dense_collectives": coll["dense"],
        "compressed_collectives": coll["compressed"],
    }
    # Acceptance: the compiled wire must realize >= 4x fewer collective
    # bytes per consensus round, and the dense path must be a single
    # all-reduce of the (m, r) factor per round (no stray collectives).
    assert row["measured_ratio"] >= 4.0, row
    assert dense == ANCHOR_ROUNDS * d * 4, coll["dense"]
    assert comp == ANCHOR_ROUNDS * k * 8 * ANCHOR_E, coll["compressed"]
    return row


def _model_rows(scales) -> list[dict]:
    rows = []
    for e in scales:
        frac = BUDGET / e
        model = consensus_wire_model(ANCHOR_M, ANCHOR_RANK, e,
                                     CompressConfig(topk_frac=frac))
        rows.append({
            "bench": "consensus",
            "name": f"model_e{e}",
            "clients": e,
            "topk_frac": frac,
            "k": model["k"],
            "dense_bytes_client_round": model["dense_bytes"],
            "compressed_bytes_client_round": model["shipped_bytes"],
            "model_ratio": model["ratio"],
        })
    return rows


def _quality_row() -> dict:
    p = generate_problem(jax.random.PRNGKey(0), 96, 128, rank=4,
                         sparsity=0.05)
    dense = DCFConfig.tuned(4, outer_iters=60)
    comp = DCFConfig.tuned(4, outer_iters=60,
                           consensus_compress=CompressConfig(
                               topk_frac=0.1))
    r_d = dcf_pca(p.m_obs, dense, num_clients=4, key=jax.random.PRNGKey(1))
    r_c = dcf_pca(p.m_obs, comp, num_clients=4, key=jax.random.PRNGKey(1))
    e_d = float(relative_error(r_d.l, r_d.s, p.l0, p.s0))
    e_c = float(relative_error(r_c.l, r_c.s, p.l0, p.s0))
    assert e_c <= 2.0 * e_d, (e_c, e_d)  # matched-recovery acceptance
    return {
        "bench": "consensus",
        "name": "quality_e4",
        "topk_frac": 0.1,
        "err_dense": e_d,
        "err_compressed": e_c,
        "err_ratio": e_c / e_d,
    }


def _wall(p, cfg, clients) -> float:
    r = dcf_pca(p.m_obs, cfg, num_clients=clients,
                key=jax.random.PRNGKey(2))
    jax.block_until_ready(r.l)  # warm compile
    start = time.perf_counter()
    r = dcf_pca(p.m_obs, cfg, num_clients=clients,
                key=jax.random.PRNGKey(2))
    jax.block_until_ready(r.l)
    return time.perf_counter() - start


def _weak_scaling_rows(scales, n_i=32) -> list[dict]:
    rows = []
    base = None
    for e in scales:
        p = generate_problem(jax.random.PRNGKey(3), 128, n_i * e, rank=4,
                             sparsity=0.05)
        cfg = DCFConfig.tuned(
            4, outer_iters=30,
            consensus_compress=CompressConfig(topk_frac=BUDGET / e))
        wall = _wall(p, cfg, e)
        per_client = wall / e
        if base is None:
            base = per_client
        rows.append({
            "bench": "consensus",
            "name": f"weak_e{e}",
            "clients": e,
            "n": n_i * e,
            "wall_s": wall,
            "per_client_eff": base / per_client,
        })
    # guard row: the endpoint efficiency under one stable name
    rows.append({
        "bench": "consensus",
        "name": "weak_scaling",
        "clients": scales[-1],
        "per_client_eff": rows[-1]["per_client_eff"],
    })
    return rows


def run(full=False):
    fast = (not full) or os.environ.get("RPCA_BENCH_FAST", "") == "1"
    scales = (4, 16, 64) if fast else (4, 8, 16, 32, 64)
    rows = _model_rows(scales)
    rows.append(_measured_anchor())
    rows.append(_quality_row())
    rows.extend(_weak_scaling_rows(scales))
    return rows


def main(full=False):
    rows = run(full=full)
    for r in rows:
        if r["name"].startswith("model_"):
            print(f"consensus/{r['name']},0,"
                  f"bytes={r['compressed_bytes_client_round']:.0f};"
                  f"ratio={r['model_ratio']:.2f};k={r['k']:.0f}")
        elif r["name"] == "wire_e4":
            print(f"consensus/wire_e4,0,"
                  f"measured_ratio={r['measured_ratio']:.2f};"
                  f"dense={r['dense_bytes_client_round']:.0f};"
                  f"compressed={r['compressed_bytes_client_round']:.0f}")
        elif r["name"] == "quality_e4":
            print(f"consensus/quality_e4,0,"
                  f"err_ratio={r['err_ratio']:.2f};"
                  f"err_dense={r['err_dense']:.2e};"
                  f"err_compressed={r['err_compressed']:.2e}")
        elif r["name"].startswith("weak"):
            print(f"consensus/{r['name']},"
                  f"{1e6 * r.get('wall_s', 0):.0f},"
                  f"per_client_eff={r['per_client_eff']:.2f}")
    return rows


if __name__ == "__main__":
    main(full="--full" in sys.argv)
