"""Multi-device SPMD tests.  jax locks the device count at first init, so
these run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Mesh constructor prepended to every subprocess snippet (the snippets run
# with PYTHONPATH=src, so the repo's shared helper is importable).
PRELUDE = """
from repro.launch.mesh import make_compat_mesh as compat_mesh
"""


def run_py(code: str, timeout=900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


def test_sharded_engine_matches_simulated():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.core import *
        from repro.core.factorized import DCFConfig
        key = jax.random.PRNGKey(42)
        p = generate_problem(key, 128, 160, rank=6, sparsity=0.05)
        cfg = DCFConfig.tuned(6, outer_iters=60)
        r_sim = dcf_pca(p.m_obs, cfg, num_clients=8)
        mesh = compat_mesh((8,), ("data",))
        r_sh = dcf_pca_sharded(p.m_obs, cfg, mesh, data_axes=("data",))
        e1 = float(relative_error(r_sim.l, r_sim.s, p.l0, p.s0))
        e2 = float(relative_error(r_sh.l, r_sh.s, p.l0, p.s0))
        assert e1 < 1e-4 and e2 < 1e-4, (e1, e2)
        # identical math -> identical trajectories (same inits)
        assert abs(e1 - e2) < 1e-6, (e1, e2)
        print("OK", e1, e2)
    """)
    assert "OK" in out


#: The consensus features held to parity, one case each; their DCFConfig
#: overrides and solve arguments are built in ``PARITY_CODE``.
PARITY_CASES = (
    "plain", "ragged", "participation", "faults", "trimmed_mean",
    "coordinate_median", "divergence_screen", "topk_wire", "consensus_delay",
)

PARITY_CODE = """
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.core import dcf_pca, dcf_pca_sharded, generate_problem
    from repro.core.factorized import DCFConfig
    from repro.distributed.faults import FaultPlan
    from repro.distributed.grad_compress import CompressConfig
    E, R, M = 8, 6, 128
    mesh = compat_mesh((E,), ("data",))
    cases = {
        "plain": ({}, {}),
        "ragged": ({}, {"n": 150}),
        "participation": ({}, {"participation": 0.6}),
        "faults": ({}, {"faults": FaultPlan.random(
            3, 40, E, {"crash": 0.1, "flaky": 0.1, "stale": 0.1})}),
        "trimmed_mean": ({"aggregator": "trimmed_mean"}, {}),
        "coordinate_median": ({"aggregator": "coordinate_median"}, {}),
        "divergence_screen": ({"divergence_screen": 3.0}, {}),
        "topk_wire": ({"consensus_compress": CompressConfig(
            topk_frac=0.25)}, {}),
        "consensus_delay": ({"consensus_delay": 1}, {}),
    }
    key = jax.random.PRNGKey(9)
    starts = {}

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    for name, (over, kw) in cases.items():
        kw = dict(kw)
        n = kw.pop("n", 160)
        p = generate_problem(jax.random.PRNGKey(3), M, n, rank=R,
                             sparsity=0.05)
        if n not in starts:
            # One start for both engines, 20 plain rounds in: the
            # simulated V is client-blocked over the padded columns, the
            # sharded V global.
            pre = dcf_pca(p.m_obs, DCFConfig.tuned(R, outer_iters=20), E,
                          key)
            starts[n] = pre.u, pre.v
        u0, v_sim = starts[n]
        v_sh = v_sim.reshape(-1, R)[:n]
        cfg = dataclasses.replace(DCFConfig.tuned(R, outer_iters=40), **over)
        a = dcf_pca(p.m_obs, cfg, E, key, warm=(u0, v_sim), **kw)
        b = dcf_pca_sharded(p.m_obs, cfg, mesh, key=key, warm=(u0, v_sh),
                            **kw)
        print(json.dumps({"case": name, "u": rel(b.u, a.u),
                          "l": rel(b.l, a.l), "s": rel(b.s, a.s),
                          "err": rel(a.l, p.l0)}))
"""


@pytest.fixture(scope="module")
def parity():
    """Every parity case, simulated against sharded engine, from one
    subprocess on eight CPU devices."""
    out = run_py(PARITY_CODE)
    rows = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    return {r["case"]: r for r in rows}


@pytest.mark.parametrize("case", PARITY_CASES)
def test_simulated_and_sharded_engines_agree(parity, case):
    """The one client step under ``vmap`` and under ``shard_map``, from the
    same start: U, L and S agree to float rounding (the eight-device
    all-reduce may sum in another order than the stacked sum) in every
    consensus feature, on a solve that recovers.  The top-k wire's
    selection turns a rounding difference into another kept entry now
    and then, so its bound is wider (5e-5 on the CPU, the same before the
    engines shared one step)."""
    r = parity[case]
    tol = 5e-4 if case == "topk_wire" else 1e-5
    assert max(r["u"], r["l"], r["s"]) < tol, r
    assert r["err"] < 0.1, r


def test_sharded_engine_row_sharding():
    """2-D sharding: rows over 'model' (the beyond-paper extension)."""
    out = run_py("""
        import jax
        from repro.core import *
        from repro.core.factorized import DCFConfig
        key = jax.random.PRNGKey(3)
        p = generate_problem(key, 128, 128, rank=5, sparsity=0.05)
        cfg = DCFConfig.tuned(5, outer_iters=60)
        mesh = compat_mesh((4, 2), ("data", "model"))
        r = dcf_pca_sharded(p.m_obs, cfg, mesh, data_axes=("data",),
                            model_axis="model")
        e = float(relative_error(r.l, r.s, p.l0, p.s0))
        assert e < 1e-4, e
        print("OK", e)
    """)
    assert "OK" in out


def test_sharded_engine_masked():
    """Observation mask sharded like M: all-ones mask is bit-exact with the
    unmasked sharded engine, and a 70%-observed solve still recovers."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.core import *
        from repro.core.factorized import DCFConfig
        key = jax.random.PRNGKey(5)
        p = generate_problem(key, 128, 128, rank=5, sparsity=0.05,
                             observed_frac=0.7)
        cfg = DCFConfig.tuned(5, outer_iters=60)
        mesh = compat_mesh((8,), ("data",))
        # Identical dense input for the bit test (s0 is already
        # mask-restricted; what matters is both calls see the same data).
        full = p.l0 + p.s0
        a = dcf_pca_sharded(full, cfg, mesh)
        b = dcf_pca_sharded(full, cfg, mesh, mask=jnp.ones_like(full))
        assert (a.l == b.l).all() and (a.s == b.s).all()
        cfg = DCFConfig.masked(5, observed_frac=0.7)
        r = dcf_pca_sharded(p.m_obs, cfg, mesh, mask=p.mask)
        err = completion_errors(r.l, p.l0, p.mask)
        assert float(err.observed) < 1e-2, float(err.observed)
        assert float(err.unobserved) < 5e-2, float(err.unobserved)
        print("OK", float(err.observed), float(err.unobserved))
    """)
    assert "OK" in out


def test_sharded_engine_elastic():
    """Elastic topologies on the SPMD engine: (1) an explicit all-ones
    participation schedule is bit-exact with the plain pmean path, (2) a
    ragged n % E != 0 matches the simulated engine and recovers, (3) 50%
    participation still recovers (weighted consensus, lock-step exit)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.core import *
        from repro.core.factorized import DCFConfig
        mesh = compat_mesh((8,), ("data",))
        cfg = DCFConfig.tuned(6, outer_iters=60)
        p = generate_problem(jax.random.PRNGKey(42), 128, 160, rank=6,
                             sparsity=0.05)
        a = dcf_pca_sharded(p.m_obs, cfg, mesh)
        b = dcf_pca_sharded(p.m_obs, cfg, mesh,
                            participation=jnp.ones((cfg.outer_iters, 8)))
        assert (a.l == b.l).all() and (a.s == b.s).all()
        assert (a.u == b.u).all() and (a.v == b.v).all()

        pr = generate_problem(jax.random.PRNGKey(3), 128, 150, rank=6,
                              sparsity=0.05)
        r_sh = dcf_pca_sharded(pr.m_obs, cfg, mesh)
        r_sim = dcf_pca(pr.m_obs, cfg, num_clients=8)
        assert r_sh.l.shape == (128, 150) and r_sh.v.shape == (150, 6)
        e_sh = float(relative_error(r_sh.l, r_sh.s, pr.l0, pr.s0))
        e_sim = float(relative_error(r_sim.l, r_sim.s, pr.l0, pr.s0))
        assert e_sh < 1e-4 and e_sim < 1e-4, (e_sh, e_sim)

        cfg_e = DCFConfig.elastic(6, participation=0.5, outer_iters=300)
        r = dcf_pca_sharded(p.m_obs, cfg_e, mesh, participation=0.5)
        e = float(low_rank_relative_error(r.l, p.l0))
        assert e <= 1e-2, e
        print("OK", e_sh, e_sim, e)
    """)
    assert "OK" in out


def test_robust_grad_aggregation_byzantine():
    """DCF-PCA consensus aggregation rejects a corrupted worker's sparse
    outliers, where plain all-reduce mean is polluted."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.grad_compress import (CompressConfig,
                                                     consensus_compress)
        mesh = compat_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        m, k, r = 256, 128, 4
        u0 = jax.random.normal(jax.random.PRNGKey(1), (m, r))
        # 8 workers share a rank-r signal + small noise; worker 0 corrupted.
        vs = jax.random.normal(jax.random.PRNGKey(2), (8, k, r))
        grads = jnp.einsum('mr,ekr->emk', u0, vs)
        grads += 0.01 * jax.random.normal(jax.random.PRNGKey(3), grads.shape)
        clean_mean = grads.mean(0)
        # corrupt worker 0 with gross sparse spikes (bit-flip scale)
        mask = jax.random.bernoulli(jax.random.PRNGKey(4), 0.02, (m, k))
        grads = grads.at[0].add(mask * 1e4)
        polluted_mean = grads.mean(0)

        ccfg = CompressConfig(rank=8, rounds=6)
        def agg(g):
            g = g.reshape(g.shape[1], g.shape[2])
            out = consensus_compress(g, ("data",), ccfg,
                                     jax.random.PRNGKey(7))
            return out[None]
        fn = jax.shard_map(agg, mesh=mesh,
                           in_specs=(P("data", None, None),),
                           out_specs=P("data", None, None), check_vma=False)
        robust = jax.jit(fn)(grads)[0]

        err_robust = float(jnp.linalg.norm(robust - clean_mean)
                           / jnp.linalg.norm(clean_mean))
        err_plain = float(jnp.linalg.norm(polluted_mean - clean_mean)
                          / jnp.linalg.norm(clean_mean))
        assert err_robust < 0.2, err_robust
        assert err_robust < 0.2 * err_plain, (err_robust, err_plain)
        print("OK robust", err_robust, "plain", err_plain)
    """)
    assert "OK" in out


def test_robust_train_step_runs():
    """make_robust_train_step: shard_map DP + consensus aggregation end to
    end on a tiny LM; loss finite and params move."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.distributed.sharding import ShardingRules
        from repro.distributed.grad_compress import CompressConfig
        from repro.models import get_model, params as pm
        from repro.training import optimizer as opt
        from repro.training.train_step import make_robust_train_step
        from repro.training.data import SyntheticData
        from repro.configs.base import ShapeSpec

        cfg = get_smoke_config("tinyllama-1.1b")
        model = get_model(cfg)
        mesh = compat_mesh((8,), ("data",))
        rules = ShardingRules(dp=("data",))
        params = pm.materialize(model.specs(), jax.random.PRNGKey(0))
        state = opt.init(params)
        step = make_robust_train_step(
            model, opt.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10),
            mesh, rules, CompressConfig(rank=4, rounds=2, min_dim=32))
        data = SyntheticData(cfg, ShapeSpec("t", 32, 8, "train"))
        with mesh:
            p2, s2, mets = jax.jit(step)(params, state,
                                         data.batch_at(0),
                                         jax.random.PRNGKey(1))
        loss = float(mets["loss"])
        assert jnp.isfinite(loss), loss
        moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), params, p2)
        assert max(jax.tree.leaves(moved)) > 0
        print("OK", loss)
    """)
    assert "OK" in out


def test_collective_bytes_counting():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.roofline.hlo_costs import analyze_hlo
        mesh = compat_mesh((8,), ("data",))
        x = jax.ShapeDtypeStruct((1024, 512), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data")))
        def f(x):
            def body(c, _):
                g = jnp.mean(x @ c, axis=0)   # all-reduce (512,) per trip
                return c + jnp.outer(g, g) * 0 + 1e-6, None
            y, _ = jax.lax.scan(body, jnp.eye(512), None, length=7)
            return y
        with mesh:
            comp = jax.jit(f).lower(x).compile()
        c = analyze_hlo(comp.as_text())
        ar = c.collective.get("all-reduce", 0)
        assert ar == 7 * 512 * 4, c.collective
        print("OK", dict(c.collective))
    """)
    assert "OK" in out


def test_sharded_engine_compressed_and_stale_wire():
    """Consensus wire knobs on the sharded engine (DESIGN.md Sec. 14):
    top-k + error-feedback compression and one-round-stale overlap both
    recover, and full-k compression reproduces the dense trajectory."""
    out = run_py("""
        import jax
        from repro.core import *
        from repro.core.factorized import DCFConfig
        from repro.distributed.grad_compress import CompressConfig
        key = jax.random.PRNGKey(11)
        p = generate_problem(key, 128, 160, rank=6, sparsity=0.05)
        mesh = compat_mesh((8,), ("data",))
        dense = DCFConfig.tuned(6, outer_iters=60)
        r_d = dcf_pca_sharded(p.m_obs, dense, mesh)
        e_d = float(relative_error(r_d.l, r_d.s, p.l0, p.s0))
        comp = DCFConfig.tuned(
            6, outer_iters=60,
            consensus_compress=CompressConfig(topk_frac=0.1))
        r_c = dcf_pca_sharded(p.m_obs, comp, mesh)
        e_c = float(relative_error(r_c.l, r_c.s, p.l0, p.s0))
        assert e_d < 1e-4, e_d
        assert e_c <= 2.0 * e_d, (e_c, e_d)
        full = DCFConfig.tuned(
            6, outer_iters=60,
            consensus_compress=CompressConfig(topk_frac=1.0))
        r_f = dcf_pca_sharded(p.m_obs, full, mesh)
        e_f = float(relative_error(r_f.l, r_f.s, p.l0, p.s0))
        assert abs(e_f - e_d) < 1e-5, (e_f, e_d)
        stale = DCFConfig.tuned(6, outer_iters=60, consensus_delay=1)
        r_s = dcf_pca_sharded(p.m_obs, stale, mesh)
        e_s = float(relative_error(r_s.l, r_s.s, p.l0, p.s0))
        assert e_s <= 2.0 * e_d, (e_s, e_d)
        print("OK", e_d, e_c, e_s)
    """)
    assert "OK" in out


@pytest.mark.sanitizer_incompatible("injects NaN payloads by design")
def test_sharded_engine_byzantine_robust_consensus():
    """Fault injection at the sharded consensus boundary (DESIGN.md
    Sec. 17): 2-of-8 Byzantine shards (one NaN, one 64x-corrupt) are
    quarantined by coordinate_median to <= 3x the fault-free error."""
    out = run_py("""
        import dataclasses
        import numpy as np
        import jax
        from repro.core import *
        from repro.core.factorized import DCFConfig
        from repro.distributed.faults import CORRUPT, FaultPlan
        key = jax.random.PRNGKey(13)
        p = generate_problem(key, 128, 128, rank=5, sparsity=0.05)
        cfg = DCFConfig.tuned(5, outer_iters=60)
        mesh = compat_mesh((8,), ("data",))
        base = dcf_pca_sharded(p.m_obs, cfg, mesh)
        e0 = float(relative_error(base.l, base.s, p.l0, p.s0))
        codes = FaultPlan.byzantine(60, 8, (1,), kind="nan").codes.copy()
        codes[:, 5] = CORRUPT
        plan = FaultPlan(codes)
        robust = dataclasses.replace(cfg, aggregator="coordinate_median")
        r = dcf_pca_sharded(p.m_obs, robust, mesh, faults=plan)
        e1 = float(relative_error(r.l, r.s, p.l0, p.s0))
        assert np.isfinite(e1) and e1 <= 3.0 * max(e0, 1e-6), (e0, e1)
        print("OK", e0, e1)
    """)
    assert "OK" in out


def test_sharded_engine_checkpoint_resume_bitexact():
    """Segmented checkpointing on the mesh: the snapshotting solve, the
    plain solve, and a killed-then-resumed solve all produce identical
    bytes -- including the per-client error-feedback wire carry -- and a
    carry written on mesh (8,) refuses to restore onto (4, 2)."""
    out = run_py("""
        import os, shutil, tempfile
        import numpy as np
        import jax
        from repro.core import *
        from repro.core import runtime as rt
        from repro.core.factorized import DCFConfig
        from repro.distributed.grad_compress import CompressConfig
        key = jax.random.PRNGKey(17)
        p = generate_problem(key, 128, 128, rank=5, sparsity=0.05)
        cfg = DCFConfig.tuned(
            5, outer_iters=24,
            consensus_compress=CompressConfig(topk_frac=0.5))
        mesh = compat_mesh((8,), ("data",))
        run = rt.RunConfig(mode="scan", checkpoint_every=9)
        plain = dcf_pca_sharded(p.m_obs, cfg, mesh)
        d = tempfile.mkdtemp()
        full = dcf_pca_sharded(p.m_obs, cfg, mesh, run=run,
                               checkpoint_dir=d)
        assert np.asarray(full.l).tobytes() == np.asarray(plain.l).tobytes()
        # kill at the first snapshot: drop the later ones, resume
        steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
        assert len(steps) >= 2, steps
        for s in steps[1:]:
            shutil.rmtree(os.path.join(d, s))
        open(os.path.join(d, "LATEST"), "w").write(
            str(int(steps[0].split("_")[1])))
        res = dcf_pca_sharded(p.m_obs, cfg, mesh, run=run, resume_from=d)
        for name in ("l", "s", "u", "v"):
            a = np.asarray(getattr(full, name))
            b = np.asarray(getattr(res, name))
            assert a.tobytes() == b.tobytes(), name
        np.testing.assert_array_equal(np.asarray(full.stats.residual),
                                      np.asarray(res.stats.residual))
        mesh2 = compat_mesh((4, 2), ("data", "model"))
        try:
            dcf_pca_sharded(p.m_obs, cfg, mesh2, data_axes=("data",),
                            model_axis="model", run=run, resume_from=d)
            raise SystemExit("changed-mesh resume was not rejected")
        except ValueError as e:
            assert "mesh" in str(e), e
        print("OK")
    """)
    assert "OK" in out
