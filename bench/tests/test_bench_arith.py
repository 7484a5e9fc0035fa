"""End-to-end arithmetic and the choice of each cell's metrics."""
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench.drivers import solve_loop  # noqa: E402


def test_solve_s_is_the_whole_window_over_its_solves():
    # Seven back-to-back solves in 10.5 s: 1.5 s each, whatever their own
    # spread; the window is never split into chunks and averaged.
    c = {"window_s": 10.5, "solves": 7}
    assert solve_loop.end_to_end(c)["solve_s"] == pytest.approx(1.5)


@pytest.mark.parametrize("value", [math.inf, -math.inf, math.nan, None])
def test_a_value_json_cannot_hold_prints_as_null(value):
    assert harness.finite(value) is None
    assert harness.finite(1.25) == 1.25


def test_each_cell_reports_setup_an_end_to_end_and_a_layer_metric():
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        e2e, layer = harness.cell_metrics(bench, w["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert layer and all(m["moves"] in names for m in layer)
