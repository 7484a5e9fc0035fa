"""Device time by program scope, read from a profiler trace.

The program names the phases of a solve with ``jax.named_scope``:
``rpca.calibrate`` (the threshold's medians), ``rpca.round`` (one solver
round), and inside a round ``rpca.ridge`` (the r x r Grams,
factorizations and solves) and ``rpca.consensus`` (the aggregation of the
client factors).  XLA keeps the name stack in each instruction's
``op_name``, and the profiler writes it into the ``.xplane.pb`` as the
``tf_op`` stat of the op's event metadata.  ``jax.profiler.ProfileData``
shows only the stats of events, not of their metadata, so this module
reads the XSpace protobuf itself, with a schema of just the fields it
needs (``XSPACE`` below; field numbers from
``tsl/profiler/protobuf/xplane.proto``).

Times equal ``ProfileData``'s: a line's ``timestamp_ns`` plus the event's
``offset_ps`` in whole nanoseconds.  Devices, the ``XLA Ops`` line and
the container rule are ``trace_events.load``'s, so the busy time of all
ops here is ``trace_events.busy_s`` of the same trace.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from pathlib import Path

from bench import trace_events as te

PREFIX = "rpca."
CALIBRATE = "rpca.calibrate"
ROUND = "rpca.round"
RIDGE = "rpca.ridge"
CONSENSUS = "rpca.consensus"


@dataclass(frozen=True)
class ScopedOp:
    """One device operation: times in seconds, and the ``rpca.*``
    components of its name stack."""

    start: float
    end: float
    scopes: frozenset[str]


def scopes_of(tf_op: str) -> frozenset[str]:
    """The ``rpca.*`` components of a name stack, such as
    ``jit(_solve)/while/body/rpca.round/rpca.ridge/jit(_cholesky)/cholesky:``
    (``{rpca.round, rpca.ridge}``)."""
    return frozenset(c for c in tf_op.rstrip(":").split("/")
                     if c.startswith(PREFIX))


# ---------------------------------------------------------------------------
# The XSpace schema: message -> [(field, number, type or message)]
# ---------------------------------------------------------------------------
XSPACE = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
}

_CLASSES: dict = {}


def _message(name: str):
    """The protobuf class of one XSpace message (built once)."""
    if not _CLASSES:
        from google.protobuf import (descriptor_pb2, descriptor_pool,
                                     message_factory)

        fd = descriptor_pb2.FileDescriptorProto(
            name="bench_xspace.proto", package="bench_xspace",
            syntax="proto3")
        kinds = descriptor_pb2.FieldDescriptorProto
        scalar = {"string": kinds.TYPE_STRING, "int64": kinds.TYPE_INT64,
                  "uint64": kinds.TYPE_UINT64}
        for msg, fields in XSPACE.items():
            m = fd.message_type.add(name=msg)
            for field, number, kind, repeated in fields:
                f = m.field.add(
                    name=field, number=number,
                    label=(kinds.LABEL_REPEATED if repeated
                           else kinds.LABEL_OPTIONAL))
                if kind in scalar:
                    f.type = scalar[kind]
                else:
                    f.type = kinds.TYPE_MESSAGE
                    f.type_name = f".bench_xspace.{kind}"
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        for msg in XSPACE:
            _CLASSES[msg] = message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"bench_xspace.{msg}"))
    return _CLASSES[name]


def _tf_op(stats, stat_names: dict[int, str]) -> str | None:
    for s in stats:
        if stat_names.get(s.metadata_id) == "tf_op":
            return s.str_value or stat_names.get(s.ref_value)
    return None


# ---------------------------------------------------------------------------
# Loading, cached per file
# ---------------------------------------------------------------------------
_CACHE: dict[tuple[str, int, int], dict[str, list[ScopedOp]]] = {}


def newest(path: str) -> str | None:
    """``path`` itself, or the newest ``.xplane.pb`` under a directory."""
    if not os.path.isdir(path):
        return path if os.path.exists(path) else None
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def load(path: str) -> dict[str, list[ScopedOp]]:
    """Each device's leaf operations with their scopes, sorted by start;
    parsed once per file (the readers of one run share it)."""
    path = newest(path)
    if path is None:
        return {}
    stat = os.stat(path)
    key = (os.path.abspath(path), stat.st_mtime_ns, stat.st_size)
    if key not in _CACHE:
        space = _message("XSpace")()
        space.ParseFromString(Path(path).read_bytes())
        _CACHE[key] = _devices(space)
    return _CACHE[key]


def _devices(space) -> dict[str, list[ScopedOp]]:
    out: dict[str, list[ScopedOp]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        containers, scoped = set(), {}
        for e in plane.event_metadata:
            if te.parse_hlo(e.value.name)[1] in te.CONTAINERS:
                containers.add(e.key)
            tf_op = _tf_op(e.value.stats, stat_names)
            if tf_op:
                scoped[e.key] = scopes_of(tf_op)
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if ev.metadata_id in containers:
                    continue
                tf_op = _tf_op(ev.stats, stat_names)
                scopes = (scopes_of(tf_op) if tf_op
                          else scoped.get(ev.metadata_id, frozenset()))
                t0 = (line.timestamp_ns + ev.offset_ps // 1000) * 1e-9
                ops.append(ScopedOp(t0, t0 + (ev.duration_ps // 1000) * 1e-9,
                                    scopes))
        if ops:
            ops.sort(key=lambda o: o.start)
            out[plane.name] = ops
    return out


# ---------------------------------------------------------------------------
# Device seconds in a window
# ---------------------------------------------------------------------------
def seconds(ops: list[ScopedOp], lo: float, hi: float, scope=None) -> float:
    """Device-busy seconds in ``[lo, hi]`` of the ops under ``scope``
    (``None``: the ops under no ``rpca.*`` scope)."""
    return te.busy_s([o for o in ops if (scope in o.scopes if scope
                                         else not o.scopes)], lo, hi)


def trace_dir(ctx) -> str:
    """Where ``harness.run_cell`` writes a traced run's profile."""
    return str(ctx.cell.root / "bench" / "out" / "trace" / ctx.cell.name)


def mean_seconds(ctx, scope=None) -> float | None:
    """Mean over devices of the window's device seconds under ``scope``
    (``None``: under no scope).  ``None`` where the trace holds no window,
    the program names no ``rpca.*`` scope at all (a program built before
    the scopes), or nothing ran under ``scope``."""
    win = ctx.trace.window() if ctx.trace is not None else None
    devices = load(trace_dir(ctx)) if win is not None else {}
    if not devices or not any(o.scopes for ops in devices.values()
                              for o in ops):
        return None
    if scope is not None and not any(scope in o.scopes
                                     for ops in devices.values()
                                     for o in ops):
        return None
    vals = [seconds(ops, *win, scope) for ops in devices.values()]
    return sum(vals) / len(vals)


def ms_per(ctx, scope: str, count: int) -> float | None:
    """Device milliseconds under ``scope`` over ``count`` (the window's
    solves or rounds, from the counters the other readers use)."""
    s = mean_seconds(ctx, scope) if count else None
    return None if s is None else s * 1e3 / count


def unscoped_pct(ctx) -> float | None:
    """Percent of the window's device-busy time under no ``rpca.*`` scope
    (means over the devices); ``None`` as :func:`mean_seconds`."""
    unscoped = mean_seconds(ctx, None)
    if unscoped is None:
        return None
    win = ctx.trace.window()
    devices = load(trace_dir(ctx))
    total = sum(te.busy_s(ops, *win) for ops in devices.values())
    total /= len(devices)
    return 100.0 * unscoped / total if total > 0 else None
