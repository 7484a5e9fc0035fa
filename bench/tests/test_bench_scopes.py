"""Device time by program scope (``bench/scopes.py``) and its readers: on
made-up events written as an XSpace protobuf, on a recorded chip trace of
a program built before the scopes, and on one recorded with them."""
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import harness, scopes  # noqa: E402
from bench import trace_events as te  # noqa: E402

DATA = Path(__file__).parent / "data"
READERS = ("calibrate_ms_per_solve", "round_device_ms", "ridge_ms_per_round",
           "consensus_ms_per_round", "unscoped_device_pct")
TS = 1000  # the line's timestamp, ns
ROUNDS = 12  # of the recorded dcf solve

SORT = "%sort.1 = f32[64]{0} sort(f32[64]{0} %p), dimensions={0}"
KERNEL = ("%huber_contract_v.3 = f32[8,128]{1,0} custom-call(f32[8,128] %a), "
          'custom_call_target="tpu_custom_call"')
CHOL = "%custom-call.6 = f32[4,4]{1,0} custom-call(f32[4,4] %g)"
MEAN = "%fusion.2 = f32[8,4]{1,0} fusion(f32[4,8,4] %u), kind=kLoop"
COPY = "%copy.1 = f32[8,4]{1,0} copy(f32[8,4] %u)"
WHILE = "%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b"
ROUND = "jit(_solve)/while/body/closed_call/rpca.round"

#: device -> [(start ns, duration ns, HLO text, tf_op or None)]; the
#: window is [0, 1000) ns past the line's timestamp.
EVENTS = {
    "/device:TPU:0": [
        (0, 1000, WHILE, "jit(_solve)/while:"),  # a container: dropped
        (0, 100, SORT, "jit(_solve)/rpca.calibrate/jit(median)/sort:"),
        (100, 200, KERNEL, ROUND + "/jit(huber_contract_v)/pallas_call:"),
        (300, 50, CHOL, ROUND + "/rpca.ridge/jit(_cholesky)/cholesky:"),
        (350, 50, MEAN, "ref:" + ROUND + "/rpca.consensus/reduce_sum:"),
        (400, 50, COPY, None),  # no tf_op at all
        (450, 50, COPY, "jit(_solve)/copy:"),  # a name stack, no scope
        (900, 200, KERNEL, ROUND + "/jit(huber_contract_v)/pallas_call:"),
    ],
    "/device:TPU:1": [
        (0, 50, SORT, "jit(_solve)/rpca.calibrate/jit(median)/sort:"),
        (50, 200, KERNEL, ROUND + "/jit(huber_contract_v)/pallas_call:"),
        (250, 50, COPY, None),
    ],
}


def _write_space(path: Path, devices) -> None:
    """An ``.xplane.pb`` holding ``devices``' events on their ``XLA Ops``
    lines, a host plane beside them, and one ``tf_op`` held by reference
    (``ref:`` names a stat metadata entry)."""
    space = scopes._message("XSpace")()
    host = space.planes.add(name="/host:CPU")
    host.lines.add(name="XLA Ops").events.add(metadata_id=1, duration_ps=9)
    for name, events in devices.items():
        plane = space.planes.add(name=name)
        tf_op = plane.stat_metadata.add(key=7)
        tf_op.value.name = "tf_op"
        line = plane.lines.add(name="XLA Ops", timestamp_ns=TS)
        for i, (t0, dur, hlo, stack) in enumerate(events, start=1):
            md = plane.event_metadata.add(key=i)
            md.value.name = hlo
            if stack and stack.startswith("ref:"):
                ref = plane.stat_metadata.add(key=100 + i)
                ref.value.name = stack[4:]
                md.value.stats.add(metadata_id=7, ref_value=100 + i)
            elif stack:
                md.value.stats.add(metadata_id=7, str_value=stack)
            line.events.add(metadata_id=i, offset_ps=t0 * 1000 + 250,
                            duration_ps=dur * 1000 + 500)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space.SerializeToString())


def _ctx(root: Path, trace_file: Path, window, rounds) -> harness.Context:
    """A traced run's context whose profile is ``trace_file``."""
    cell = harness.Cell(name="cell", chips=1, config={}, traffic={},
                        entry={}, root=root)
    dst = Path(scopes.trace_dir(harness.Context(cell, {}, None, "")))
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_file, dst / "run.xplane.pb")
    trace = te.Trace(spans=[("window", *window)]) if window else None
    return harness.Context(cell=cell, counters={"rounds": rounds},
                           trace=trace, device_kind="TPU v5 lite")


def _read(ctx) -> dict:
    out = {}
    for name in READERS:
        reader = harness.load_module(
            harness.ROOT / "bench" / "layer_metrics" / f"{name}.py",
            "bench_metric_" + name)
        out[name] = reader.read(ctx)
    return out


def test_scopes_of_a_name_stack():
    assert scopes.scopes_of(ROUND + "/rpca.ridge/jit(_cholesky)/cholesky:") \
        == {"rpca.round", "rpca.ridge"}
    assert scopes.scopes_of("jit(_solve)/jit(median)/sort:") == set()


def test_made_up_events(tmp_path):
    _write_space(tmp_path / "space.xplane.pb", EVENTS)
    devices = scopes.load(str(tmp_path / "space.xplane.pb"))
    assert sorted(devices) == ["/device:TPU:0", "/device:TPU:1"]
    ops = devices["/device:TPU:0"]
    assert len(ops) == 7  # the while is a container
    # Whole nanoseconds, as ProfileData gives them.
    assert ops[0].start == pytest.approx(TS * 1e-9)
    assert ops[0].end - ops[0].start == pytest.approx(100e-9)
    lo, hi = TS * 1e-9, (TS + 1000) * 1e-9
    ns = {s: scopes.seconds(ops, lo, hi, s) * 1e9
          for s in (scopes.CALIBRATE, scopes.ROUND, scopes.RIDGE,
                    scopes.CONSENSUS, None)}
    # Nested scopes count in the round too; the last kernel is clipped to
    # the window; no tf_op and a name stack without a scope are unscoped.
    assert ns == pytest.approx({scopes.CALIBRATE: 100, scopes.ROUND: 400,
                                scopes.RIDGE: 50, scopes.CONSENSUS: 50,
                                None: 100})
    assert te.busy_s(ops, lo, hi) * 1e9 == pytest.approx(600)

    ctx = _ctx(tmp_path, tmp_path / "space.xplane.pb", (lo, hi), [2])
    got = _read(ctx)
    # Means over the two devices: calibrate 75 ns a solve, the round 300 ns
    # over 2 rounds, ridge and consensus 25 ns over 2 rounds, 75 ns of 450
    # unscoped.
    assert got == pytest.approx({
        "calibrate_ms_per_solve": 75e-6, "round_device_ms": 150e-6,
        "ridge_ms_per_round": 12.5e-6, "consensus_ms_per_round": 12.5e-6,
        "unscoped_device_pct": 100 * 75 / 450})


def test_made_up_events_nothing_to_read(tmp_path):
    """No window, no rounds, a scope that never ran: ``None``, not 0."""
    _write_space(tmp_path / "a.xplane.pb", {"/device:TPU:0": [
        (0, 100, SORT, "jit(_solve)/rpca.calibrate/jit(median)/sort:")]})
    lo, hi = TS * 1e-9, (TS + 1000) * 1e-9
    got = _read(_ctx(tmp_path, tmp_path / "a.xplane.pb", (lo, hi), [3]))
    assert got["calibrate_ms_per_solve"] == pytest.approx(100e-6)
    assert got["round_device_ms"] is None
    assert got["consensus_ms_per_round"] is None
    assert got["unscoped_device_pct"] == 0.0
    assert set(_read(_ctx(tmp_path, tmp_path / "a.xplane.pb", None,
                          [3])).values()) == {None}
    assert _read(_ctx(tmp_path, tmp_path / "a.xplane.pb", (lo, hi),
                      []))["calibrate_ms_per_solve"] is None


def test_program_without_scopes(tmp_path):
    """The trace of a program built before the scopes (cf at 1024^2, four
    rounds, on one v5e): every op is unscoped, the clocks agree with
    ``trace_events``, and every reader leaves its metric out."""
    path = DATA / "cf_small.xplane.pb"
    devices = scopes.load(str(path))
    trace = te.load(str(path))
    assert list(devices) == list(trace.devices) == ["/device:TPU:0"]
    ops = devices["/device:TPU:0"]
    assert not any(o.scopes for o in ops)
    lo, hi = trace.spans[0][1], trace.spans[-1][2]
    assert [(o.start, o.end) for o in ops] == [
        (o.start, o.end) for o in trace.devices["/device:TPU:0"]]
    assert scopes.seconds(ops, lo, hi) == te.busy_s(
        trace.devices["/device:TPU:0"], lo, hi)
    got = _read(_ctx(tmp_path, path, (lo, hi), [4, 4]))
    assert set(got.values()) == {None}


@pytest.fixture(scope="module")
def scoped():
    # A masked dcf solve at 1024^2, rank 16, 4 clients, 12 rounds, recorded
    # on one v5e chip inside a bench.window span
    # (bench/tests/record_dcf_small.py).
    path = DATA / "dcf_small.xplane.pb"
    return path, te.load(str(path))


def test_recorded_scopes_cover_the_window(scoped, tmp_path):
    path, trace = scoped
    win = trace.window()
    got = _read(_ctx(tmp_path, path, win, [ROUNDS]))
    assert all(got[name] > 0 for name in READERS), got
    assert got["unscoped_device_pct"] < 2.0
    # Calibration once, the rounds, and the unscoped rest make up the
    # device-busy time; the clocks agree with trace_events.
    (ops,) = scopes.load(str(path)).values()
    (te_ops,) = trace.devices.values()
    busy = te.busy_s(te_ops, *win)
    assert [(o.start, o.end) for o in ops] == [
        (o.start, o.end) for o in te_ops]
    parts = (got["calibrate_ms_per_solve"] + ROUNDS * got["round_device_ms"]
             ) * 1e-3 + scopes.seconds(ops, *win)
    assert parts == pytest.approx(busy, rel=1e-2)
    # The ridge and consensus nest inside the round.
    assert got["ridge_ms_per_round"] + got["consensus_ms_per_round"] < \
        got["round_device_ms"]
    # The calibration and the round never share an op.
    assert not any({scopes.CALIBRATE, scopes.ROUND} <= o.scopes for o in ops)
