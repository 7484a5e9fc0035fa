"""Operations and bytes of the contraction layer, counted from shapes."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import peaks, work  # noqa: E402


def _expected(m, nb, r, blocks, rounds, k, j, masked, solves=1):
    plane = m * nb * 4 + (m * nb / 8 if masked else 0)
    sweep_b = plane + (m + nb) * r * 4 + nb * r * 4
    ustep_b = plane + (m + nb) * r * 4 + m * r * 4
    shrink_b = plane + (m + nb) * r * 4 + m * nb * 4
    flops = (rounds * k * (j + 1) * 4 * m * nb * r + 2 * m * nb * r)
    bytes_ = rounds * k * (j * sweep_b + ustep_b) + shrink_b
    return flops * blocks * solves, bytes_ * blocks * solves


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("m,n,r,blocks,rounds", [
    (8192, 8192, 128, 1, 100),   # cf-8192
    (8192, 8192, 128, 16, 429),  # dcf16-8192-obs80's shapes
    (2048, 1024, 32, 4, 7),
])
def test_solve_work_matches_hand_count(m, n, r, blocks, rounds, masked):
    w = work.solve_work(m, n, r, blocks=blocks, rounds=rounds,
                        local_iters=2, inner_sweeps=3, masked=masked,
                        solves=3)
    flops, bytes_ = _expected(m, n // blocks, r, blocks, rounds, 2, 3,
                              masked, solves=3)
    assert w.flops == pytest.approx(flops, rel=1e-12)
    assert w.bytes == pytest.approx(bytes_, rel=1e-12)


def test_cf_round_numbers():
    # One cf round at 8192^2, r=128: 8 passes of 34.4 GFLOP, 281 MB each.
    w = work.solve_work(8192, 8192, 128, blocks=1, rounds=1, local_iters=2,
                        inner_sweeps=3, masked=False)
    shrink = work.shrink_pass(8192, 8192, 128, masked=False)
    assert w.flops - shrink.flops == 8 * 4 * 8192 ** 2 * 128
    assert w.bytes - shrink.bytes == pytest.approx(
        8 * 8192 ** 2 * 4 + 8 * 2 * 8192 * 128 * 4 + 8 * 8192 * 128 * 4)


def test_mask_costs_one_bit_an_entry():
    plain = work.contraction_pass(4096, 2048, 64, masked=False,
                                  out_rows=2048)
    masked = work.contraction_pass(4096, 2048, 64, masked=True,
                                   out_rows=2048)
    assert masked.flops == plain.flops
    assert masked.bytes - plain.bytes == 4096 * 2048 / 8


def test_least_time_names_the_binding_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_time(work.Work(1e12, 1e9), v5e.flops_bf16,
                               v5e.hbm_bw)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)
    t, bound = work.least_time(work.Work(1e9, 1e9), v5e.flops_bf16,
                               v5e.hbm_bw)
    assert bound == "memory" and t == pytest.approx(1e9 / 819e9)
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
