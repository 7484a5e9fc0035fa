"""Loop-aware HLO cost analyzer: validated against known workloads.
(XLA's builtin cost_analysis counts while bodies once -- the reason this
module exists; see EXPERIMENTS.md Sec. Dry-run.)"""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis
from repro.roofline.hlo_costs import analyze_hlo


def _scan_matmul(n, side=256):
    def body(c, _):
        return jnp.tanh(c @ c), None

    def g(x):
        y, _ = jax.lax.scan(body, x, None, length=n)
        return y

    x = jnp.zeros((side, side))
    return jax.jit(g).lower(x).compile()


@pytest.mark.parametrize("n", [1, 5, 23])
def test_flops_scale_with_trip_count(n):
    c = analyze_hlo(_scan_matmul(n).as_text())
    expect = n * 2 * 256**3
    assert abs(c.flops - expect) / expect < 0.01


def _builtin_flops(compiled):
    return compiled.cost_analysis()["flops"]


def test_builtin_cost_analysis_undercounts():
    """Documents WHY we parse HLO: XLA counts the while body once."""
    f5 = _builtin_flops(_scan_matmul(5))
    f1 = _builtin_flops(_scan_matmul(1))
    assert abs(f5 - f1) / f1 < 0.05


def test_nested_scan():
    def nested(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=5)
            return jnp.tanh(y), None

        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    x = jnp.zeros((128, 128))
    c = analyze_hlo(jax.jit(nested).lower(x).compile().as_text())
    expect = 20 * 2 * 128**3
    assert abs(c.flops - expect) / expect < 0.02


def test_bytes_unique_convention():
    """One matmul: bytes ~= inputs read + output written (not operand
    re-reads)."""
    def f(a, b):
        return a @ b

    a = jnp.zeros((512, 512))
    c = analyze_hlo(jax.jit(f).lower(a, a).compile().as_text())
    expect = 3 * 512 * 512 * 4  # two param reads + one result write
    assert c.bytes <= 1.5 * expect


def test_roofline_terms_and_bottleneck():
    r = analysis.Roofline(
        device_kind="TPU v5 lite", arch="x", shape="y", mesh="16x16", n_devices=256,
        flops_per_device=197e12 * 0.010,  # 10 ms compute
        bytes_per_device=819e9 * 0.002,  # 2 ms memory
        coll_bytes_per_device=50e9 * 0.004,  # 4 ms collective
        coll_breakdown={}, model_flops_global=197e12 * 256 * 0.008,
        peak_memory_per_device=1e9,
    )
    assert abs(r.t_compute - 0.010) < 1e-12
    assert r.bottleneck == "compute"
    assert abs(r.useful_flops_ratio - 0.8) < 1e-9
    assert abs(r.roofline_fraction - 0.8) < 1e-9


def test_unknown_device_kind_rejected():
    """A chip with no peaks entry is an error, not a silent v5e default."""
    assert analysis.peaks_for("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError, match="no peaks for device_kind"):
        analysis.peaks_for("cpu")
    with pytest.raises(ValueError, match="no peaks for device_kind"):
        analysis.Roofline(
            device_kind="TPU v9 imaginary", arch="x", shape="y", mesh="1",
            n_devices=1, flops_per_device=1.0, bytes_per_device=1.0,
            coll_bytes_per_device=0.0, coll_breakdown={},
            model_flops_global=1.0, peak_memory_per_device=0.0,
        )


def test_dryrun_artifacts_complete():
    """The committed dry-run sweep covers every (arch x shape x mesh) cell
    the assignment requires (long_500k only for ssm/hybrid)."""
    import json
    import os

    from repro.configs import ARCH_IDS, SHAPES, get_config, supports_shape

    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "dryrun_results")
    if not os.path.isdir(out_dir) or not os.listdir(out_dir):
        pytest.skip("dry-run sweep not yet executed")
    missing = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            ok, _ = supports_shape(get_config(arch), SHAPES[shape])
            if not ok:
                continue
            for mesh in ("16x16", "2x16x16"):
                tag = f"{arch}__{shape}__{mesh}.json"
                if not os.path.exists(os.path.join(out_dir, tag)):
                    missing.append(tag)
    assert not missing, missing
    # every record has the three terms
    sample = json.load(open(os.path.join(
        out_dir, "tinyllama-1.1b__train_4k__16x16.json")))
    for k in ("t_compute", "t_memory", "t_collective", "bottleneck",
              "useful_flops_ratio", "peak_memory_per_device"):
        assert k in sample
