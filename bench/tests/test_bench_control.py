"""The control -- the plain reference in the program's place, one bf16
pass a product -- reads as not correct in every cell, at a size a CPU
test run can hold; the same reference at full f32 passes where the
program does."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import control, harness, testing  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.tiny_root(tmp_path_factory.mktemp("bench"))


CELLS = ["cf-8192", "dcf16-8192-obs80", "dcf-sharded-4x8192"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_refused(root, cell):
    _, c = harness.load_cell(root, cell)
    out = control.readings(c, SEED, "bf16")
    assert out["correct"] is False
    assert out["checks"]["lowrank_err"]["value"] > 3 * \
        c.traffic["limits"]["lowrank_err"]
    assert out["checks"]["ref_gap"]["value"] > 3 * \
        c.traffic["limits"]["ref_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_reference_at_full_precision_passes(root, cell):
    _, c = harness.load_cell(root, cell)
    assert control.readings(c, SEED, "highest")["correct"] is True
