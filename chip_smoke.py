#!/usr/bin/env python3
"""Bring-up smoke run: drive the RPCA main path once on TPU chips.

    python chip_smoke.py             # one chip: device, kernels, solves, serve
    python chip_smoke.py --chips 4   # dcf_sharded on a 4-chip mesh only

Everything runs in this one process (a chip belongs to one process), through
the entry points a user calls: ``repro.rpca.solve`` and ``RPCAGateway``.
Each phase prints one line: its sizes, the cold time (first call, compile
included), the warm time (second call, ended by ``block_until_ready``), the
recovery error against the generated ground truth, and its tolerance.  The
last line of stdout is one JSON object naming the device; it is printed
only when every phase passed.

The script refuses to run, and prints no result, when JAX finds no TPU (it
never falls back to the CPU) or when ``RPCA_INTERPRET`` is set.

Tolerances.  ``rel_err`` is the paper's Eq. (30) error, held to 1e-4 as on
the CPU (``examples/quickstart.py``).  Because the gross corruptions
dominate its denominator, the low-rank error ``||L - L0||_F / ||L0||_F`` is
checked too, against :data:`LOWRANK_TOL` (the CPU reaches ~1.3e-4 at these
presets).  ``tpu_custom_call`` reports whether the lowered program holds a
Pallas kernel; the ialm path has none (its SVD is XLA's).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import rpca  # noqa: E402
from repro.core import (  # noqa: E402
    DCFConfig,
    completion_errors,
    default_cache,
    generate_problem,
    low_rank_relative_error,
    relative_error,
)
from repro.core.dcf_pca import sharded_solve_hlo  # noqa: E402
from repro.distributed.grad_compress import CompressConfig  # noqa: E402
from repro.jax_cache import enable_persistent_cache  # noqa: E402
from repro.kernels import compat as kcompat  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.mesh import make_compat_mesh  # noqa: E402
from repro.serving.gateway import GatewayConfig, RPCAGateway  # noqa: E402

#: One-chip solves: an 8192 x 8192 f32 data plane (256 MiB), rank 1.6% of
#: n, 5% gross corruptions (the paper's Sec. 4.1 generator).
SOLVE_N = 8192
SOLVE_RANK = 128
SPARSITY = 0.05
CLIENTS = 16
OBSERVED = 0.8
#: The convex baseline runs a dense SVD per iteration: 2048^2.
IALM_N = 2048
IALM_RANK = 32
#: Gateway: mixed-width tenants of one 2048-row deployment; 1024-column
#: pages give two lane widths (two tick programs to compile).
SERVE_M = 2048
SERVE_WIDTHS = (640, 2048, 1024, 1536, 896, 1792, 512, 2048)
SERVE_RANK = 32
SERVE_PAGE = 1024
#: Lanes stop a tenant once its relative residual is below this (the
#: gateway's own default, 5e-4, stops early enough to leave ~5e-3 low-rank
#: error at small sizes on the CPU).
SERVE_TOL = 1e-5
SERVE_MAX_ROUNDS = 300
#: Kernel-vs-reference check shape (f32, the reference at HIGHEST).
KERNEL_N = 2048
KERNEL_RANK = 128
#: Four-chip phase: a problem 4x as wide as the one-chip solve.
SHARDED_CHIPS = 4
TOPK_FRAC = 0.1

REL_ERR_TOL = 1e-4
LOWRANK_TOL = 1e-3
KERNEL_TOL = 1e-4
#: dcf_sharded vs simulated dcf: relative Frobenius distance of their L.
AGREE_TOL = 1e-3


class PhaseFailed(Exception):
    pass


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def report(phase: str, **fields) -> None:
    print(f"phase={phase} " + " ".join(f"{k}={_fmt(v)}"
                                       for k, v in fields.items()),
          flush=True)


def timed(fn):
    """``(result, seconds)`` with the clock stopped by block_until_ready."""
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def has_kernel(lowered_text: str) -> bool:
    return "tpu_custom_call" in lowered_text


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def device_phase(chips: int) -> jax.Device:
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise PhaseFailed(f"no TPU found: {e}") from None
    d = devices[0]
    report("device", platform=d.platform, kind=d.device_kind,
           count=len(devices), jax=jax.__version__)
    check(d.platform == "tpu", f"no TPU found: JAX runs on {d.platform!r}")
    check(len(devices) >= chips,
          f"{chips} chips requested, JAX sees {len(devices)}")
    return d


def kernels_phase(require_tpu: bool = True) -> None:
    impl = kops.resolve_impl("auto")
    interp = kcompat.should_interpret(None)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    u = jax.random.normal(k[0], (KERNEL_N, KERNEL_RANK), jnp.float32)
    v = jax.random.normal(k[1], (KERNEL_N, KERNEL_RANK), jnp.float32)
    m = u @ v.T + 4.0 * jax.random.normal(k[2], (KERNEL_N, KERNEL_N))
    lam = 3.0
    got = kops.huber_dual_contract(u, v, m, lam, impl="auto")
    with jax.default_matmul_precision("highest"):
        want = kref.huber_dual_contract(u, v, m, lam)
    err = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
              for a, b in zip(got, want))
    text = jax.jit(
        lambda u, v, m: kops.huber_dual_contract(u, v, m, lam, impl="auto")
    ).lower(u, v, m).as_text()
    report("kernels", impl=impl, interpret=interp,
           tpu_custom_call=has_kernel(text), n=KERNEL_N, rank=KERNEL_RANK,
           max_rel_diff_vs_f32_ref=err, tol=KERNEL_TOL)
    if require_tpu:
        check(impl == "pallas", f"impl='auto' resolved to {impl!r}")
        check(interp is False, "kernels would run in interpret mode")
        check(has_kernel(text), "no tpu_custom_call in the lowered kernel")
    check(err <= KERNEL_TOL, f"kernel vs f32 reference: {err:.3g}")


def solve_phase(name: str, problem, *, method: str, require_tpu: bool = True,
                num_clients: int | None = None, mesh=None, cfg=None,
                run=None):
    """Solve twice through ``rpca.solve`` (cold, warm); check recovery."""

    def spec(m_obs, mask):
        return rpca.RPCASpec(m_obs, mask=mask, rank=problem.rank,
                             num_clients=num_clients, mesh=mesh)

    def call():
        return rpca.solve(spec(problem.m_obs, problem.mask), method=method,
                          cfg=cfg, run=run)

    def parts(res):
        return res.l, res.s, res.u, res.v

    res, cold = timed(lambda: parts(call()))
    res, warm = timed(lambda: parts(call()))
    l, s = res[0], res[1]
    if mesh is None:
        text = jax.jit(
            lambda m_obs, mask: rpca.solve(spec(m_obs, mask), method=method,
                                           cfg=cfg, run=run).l
        ).lower(problem.m_obs, problem.mask).as_text()
    else:  # the sharded engine builds its program on the host
        text = sharded_solve_hlo(problem.m_obs, cfg, mesh, optimized=False,
                                 mask=problem.mask)
    rel = float(relative_error(l, s, problem.l0, problem.s0))
    low = float(low_rank_relative_error(l, problem.l0))
    fields = dict(method=method, m=problem.m_obs.shape[0],
                  n=problem.m_obs.shape[1], rank=problem.rank)
    if num_clients is not None:
        fields["clients"] = num_clients
    if mesh is not None:
        fields["devices"] = mesh.size
    if problem.mask is not None:
        ce = completion_errors(l, problem.l0, problem.mask)
        fields["observed"] = OBSERVED
        fields["lowrank_err_hidden"] = float(ce.unobserved)
    fields.update(cold_s=cold, warm_s=warm, rel_err=rel, lowrank_err=low,
                  rel_tol=REL_ERR_TOL, lowrank_tol=LOWRANK_TOL,
                  tpu_custom_call=has_kernel(text))
    report(name, **fields)
    if require_tpu and method != "ialm":  # ialm runs no Pallas kernel
        check(has_kernel(text), "no tpu_custom_call in the lowered solve")
    check(rel <= REL_ERR_TOL, f"rel_err {rel:.3g} > {REL_ERR_TOL}")
    check(low <= LOWRANK_TOL, f"lowrank_err {low:.3g} > {LOWRANK_TOL}")
    return l


def serve_phase(m: int = SERVE_M, widths=SERVE_WIDTHS, rank: int = SERVE_RANK,
                page: int = SERVE_PAGE, require_tpu: bool = True) -> None:
    tenants = [generate_problem(jax.random.PRNGKey(100 + i), m, w, rank,
                                SPARSITY)
               for i, w in enumerate(widths)]
    gcfg = GatewayConfig(page_cols=page, slots=4, rounds_per_tick=10,
                         max_rounds=SERVE_MAX_ROUNDS, tol=SERVE_TOL)
    gw = RPCAGateway(m, max(widths), DCFConfig.tuned(rank), gcfg)
    mats = [t.m_obs for t in tenants]

    def serve_all():
        return [(r.l, r.s) for r in gw.solve_all(mats)]

    out, cold = timed(serve_all)
    out, warm = timed(serve_all)
    errs = [float(relative_error(l, s, t.l0, t.s0))
            for (l, s), t in zip(out, tenants)]
    lows = [float(low_rank_relative_error(l, t.l0))
            for (l, _), t in zip(out, tenants)]
    mets = gw.metrics()
    ticks = [c for k, c in default_cache().items()
             if isinstance(k, tuple) and k and k[0] == "service_tick"]
    kernel = bool(ticks) and all(has_kernel(c.as_text()) for c in ticks)
    report("serve", m=m, widths="/".join(map(str, widths)), rank=rank,
           requests=len(widths), completed=mets["completed"],
           lanes=len(ticks), cold_s=cold, warm_s=warm,
           max_rel_err=max(errs), max_lowrank_err=max(lows),
           rel_tol=REL_ERR_TOL, lowrank_tol=LOWRANK_TOL,
           tpu_custom_call=kernel)
    if require_tpu:
        check(kernel, "a serving tick executable has no tpu_custom_call")
    check(len(out) == len(widths), "not every ticket resolved")
    check(max(errs) <= REL_ERR_TOL, f"max rel_err {max(errs):.3g}")
    check(max(lows) <= LOWRANK_TOL, f"max lowrank_err {max(lows):.3g}")


def held_bytes(arr, devices) -> dict[int, int]:
    """Bytes of ``arr`` held on each device, by device id."""
    held = {d.id: 0 for d in devices}
    for sh in arr.addressable_shards:
        held[sh.device.id] += sh.data.nbytes
    return held


def sharded_phase(n_rows: int = SOLVE_N, rank: int = SOLVE_RANK,
                  chips: int = SHARDED_CHIPS,
                  require_tpu: bool = True) -> None:
    p = generate_problem(jax.random.PRNGKey(4), n_rows, chips * n_rows,
                         rank, SPARSITY)
    mesh = make_compat_mesh((chips,), ("data",))
    devices = list(mesh.devices.flat)
    kw = dict(require_tpu=require_tpu)
    dense = DCFConfig.tuned(rank)
    l_dense = solve_phase("sharded.dense", p, method="dcf_sharded",
                          mesh=mesh, cfg=dense, **kw)
    held = held_bytes(l_dense, devices)
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use", "na")
              for d in devices}
    report("sharded.layout", l_bytes_held_per_device_of_total=",".join(
        f"{d.id}:{held[d.id]}/{in_use[d.id]}" for d in devices))
    check(all(held[d.id] == l_dense.nbytes // chips for d in devices),
          "L is not split evenly over the devices")
    topk = DCFConfig.tuned(rank, consensus_compress=CompressConfig(
        topk_frac=TOPK_FRAC))
    l_topk = solve_phase("sharded.topk", p, method="dcf_sharded", mesh=mesh,
                         cfg=topk, **kw)
    l_sim = solve_phase("sharded.reference_dcf", p, method="dcf",
                        num_clients=chips, cfg=dense, **kw)
    sim = jax.device_put(l_sim, devices[0])
    den = jnp.linalg.norm(sim)
    d_dense = float(jnp.linalg.norm(jax.device_put(l_dense, devices[0]) - sim)
                    / den)
    d_topk = float(jnp.linalg.norm(jax.device_put(l_topk, devices[0]) - sim)
                   / den)
    report("sharded.agree", dense_vs_dcf=d_dense, topk_vs_dcf=d_topk,
           tol=AGREE_TOL)
    check(d_dense <= AGREE_TOL, f"dense vs dcf {d_dense:.3g}")
    check(d_topk <= AGREE_TOL, f"topk vs dcf {d_topk:.3g}")


def one_chip_phases() -> list:
    key = jax.random.PRNGKey(0)
    p = generate_problem(key, SOLVE_N, SOLVE_N, SOLVE_RANK, SPARSITY)
    pm = generate_problem(jax.random.PRNGKey(1), SOLVE_N, SOLVE_N,
                          SOLVE_RANK, SPARSITY, observed_frac=OBSERVED)
    pi = generate_problem(jax.random.PRNGKey(2), IALM_N, IALM_N, IALM_RANK,
                          SPARSITY)
    return [
        ("kernels", kernels_phase),
        ("solve.cf", lambda: solve_phase("solve.cf", p, method="cf")),
        ("solve.dcf", lambda: solve_phase("solve.dcf", p, method="dcf",
                                          num_clients=CLIENTS)),
        ("solve.dcf_masked", lambda: solve_phase(
            "solve.dcf_masked", pm, method="dcf", num_clients=CLIENTS)),
        ("solve.ialm", lambda: solve_phase("solve.ialm", pi, method="ialm",
                                           run="early")),
        ("serve", serve_phase),
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1, help="4: run only the dcf_sharded phase")
    args = ap.parse_args()
    if os.environ.get("RPCA_INTERPRET"):
        print("RPCA_INTERPRET is set: refusing to run (it would route the "
              "kernels through interpret mode)", file=sys.stderr)
        return 2
    enable_persistent_cache()
    try:
        dev = device_phase(args.chips)
    except PhaseFailed as e:
        print(f"phase=device FAILED: {e}", file=sys.stderr)
        return 1
    if args.chips == SHARDED_CHIPS:
        phases = [("sharded", sharded_phase)]
    else:
        phases = one_chip_phases()
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - reported, then exit nonzero
            failed.append(name)
            print(f"phase={name} FAILED: {type(e).__name__}: {e}", flush=True)
            if not isinstance(e, PhaseFailed):
                traceback.print_exc()
    if failed:
        print(f"failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
