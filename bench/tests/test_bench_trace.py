"""The trace reduction, on a recorded chip trace and on made-up events."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import trace_events as te  # noqa: E402

DATA = Path(__file__).parent / "data" / "cf_small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    # A cf solve at 1024^2, rank 16, 4 rounds, recorded on one v5e chip
    # with two benchmark spans: "solve", then "check" 10 ms later.
    return te.load(str(DATA))


def test_recorded_trace_devices_and_spans(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert [s[0] for s in trace.spans] == ["solve", "check"]
    ops = trace.devices["/device:TPU:0"]
    assert all(o.opcode not in te.CONTAINERS for o in ops)
    assert all(o.end >= o.start for o in ops)


def test_recorded_trace_busy_union_and_gaps(trace):
    ops = trace.devices["/device:TPU:0"]
    lo, hi = trace.spans[0][1], trace.spans[-1][2]
    busy = te.busy_s(ops, lo, hi)
    merged = te.union(((o.start, o.end) for o in ops), lo, hi)
    idle = te.total(te.gaps(merged, lo, hi))
    assert 0 < busy < hi - lo
    assert busy + idle == pytest.approx(hi - lo, rel=1e-9)
    # The sum of op durations counts overlaps twice; the union never does.
    assert busy <= sum(o.dur for o in ops) + 1e-12
    gaps = te.labelled_gaps(ops, trace.spans, lo, hi)
    assert gaps[0][0] in ("solve", "check") and gaps[0][1] > 0.005
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)


def test_recorded_trace_kernels_by_name(trace):
    ops = trace.devices["/device:TPU:0"]
    lo, hi = trace.spans[0][1], trace.spans[-1][2]
    by = te.kernel_by_name(ops, lo, hi)
    assert {"huber_contract_v", "huber_contract_u_diag",
            "residual_shrink"} <= set(by)
    assert sum(by.values()) == pytest.approx(te.kernel_s(ops, lo, hi))
    # 3 sweeps to 1 U-step pass a local iteration, two solves traced.
    n_v = sum(1 for o in ops if o.kernel and
              o.instr.startswith("huber_contract_v"))
    n_u = sum(1 for o in ops if o.kernel and
              o.instr.startswith("huber_contract_u_diag"))
    assert n_v == 3 * n_u > 0
    # No collective on one chip.
    assert te.collective_s(ops, lo, hi) == 0.0


def _op(a, b, opcode, kernel=False):
    return te.Op(a, b, f"{opcode}.1", opcode, kernel)


def test_exposed_collective_time():
    ops = [_op(0.0, 1.0, "fusion"), _op(0.5, 2.5, "all-reduce"),
           _op(2.0, 3.0, "custom-call", kernel=True),
           _op(4.0, 4.5, "all-gather")]
    assert te.collective_s(ops, 0.0, 5.0) == pytest.approx(2.5)
    # all-reduce exposed from 1.0 to 2.0, all-gather wholly exposed.
    assert te.exposed_collective_s(ops, 0.0, 5.0) == pytest.approx(1.5)
    assert te.busy_s(ops, 0.0, 5.0) == pytest.approx(3.5)
    assert te.kernel_s(ops, 0.0, 5.0) == pytest.approx(1.0)
    assert te.gaps(te.union(((o.start, o.end) for o in ops), 0, 5),
                   0, 5) == [(3.0, 4.0), (4.5, 5.0)]


def test_parse_hlo_names():
    assert te.parse_hlo(
        "%while.18 = (s32[], f32[8,128]{1,0}) while((s32[], f32[8,128]) "
        "%tuple.1), condition=%c, body=%b") == ("while.18", "while")
    assert te.parse_hlo(
        "%huber_contract_v.3 = f32[8192,128]{1,0:T(8,128)} custom-call("
        "f32[8192,128] %a)") == ("huber_contract_v.3", "custom-call")
    assert te.parse_hlo("%all-reduce.7 = f32[4]{0} all-reduce(f32[4] %x)"
                        ) == ("all-reduce.7", "all-reduce")
