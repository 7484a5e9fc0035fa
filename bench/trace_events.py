"""Reduction of a profiler trace to device intervals and metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things: for every device, the leaf operations of its ``XLA Ops`` line
(containers such as ``while`` span their bodies and are dropped), and the
benchmark's own host spans (``bench.*`` annotations on the Python
thread).  Device and host events share the profiler's clock.  Everything
below works on plain tuples, so a test can feed it made-up events too.

An operation's name in the trace is its HLO text,
``%<instruction> = <shape> <opcode>(...)``.  Pallas kernels are custom
calls with ``custom_call_target="tpu_custom_call"``; their instruction is
named after the kernel.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
CONTAINERS = {"while", "conditional", "call"}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'

_INSTR = re.compile(r"^%?([\w.\-]+)\s*=\s*")


@dataclass(frozen=True)
class Op:
    """One device operation: times in seconds on the trace's clock."""

    start: float
    end: float
    instr: str
    opcode: str
    kernel: bool

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    devices: dict[str, list[Op]] = field(default_factory=dict)
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    def window(self) -> tuple[float, float] | None:
        """The measured window: the ``bench.window`` span."""
        for name, t0, t1 in self.spans:
            if name == "window":
                return t0, t1
        return None


def parse_hlo(text: str) -> tuple[str, str]:
    """``(instruction, opcode)`` of an HLO instruction's text."""
    m = _INSTR.match(text)
    if not m:
        return text, text
    instr = m.group(1)
    rest = text[m.end():]
    if rest.startswith("("):  # tuple shape: skip to its matching paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    opcode = rest.strip().split("(", 1)[0].strip()
    return instr, opcode


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or the newest one under a directory."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    instr, opcode = parse_hlo(ev.name)
                    if opcode in CONTAINERS:
                        continue
                    t0 = ev.start_ns * 1e-9
                    ops.append(Op(t0, t0 + ev.duration_ns * 1e-9, instr,
                                  opcode, KERNEL_MARK in ev.name))
            if ops:
                ops.sort(key=lambda o: o.start)
                trace.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        t0 = ev.start_ns * 1e-9
                        trace.spans.append((ev.name[len(SPAN_PREFIX):], t0,
                                            t0 + ev.duration_ns * 1e-9))
    trace.spans.sort(key=lambda s: s[1])
    return trace


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of merged intervals within ``[lo, hi]``."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


# ---------------------------------------------------------------------------
# Metrics over one device's operations in a window
# ---------------------------------------------------------------------------
def busy_s(ops: list[Op], lo: float, hi: float) -> float:
    """Seconds in ``[lo, hi]`` in which some operation ran."""
    return total(union(((o.start, o.end) for o in ops), lo, hi))


def kernel_s(ops: list[Op], lo: float, hi: float) -> float:
    """Seconds of Pallas kernel events in ``[lo, hi]``."""
    return sum(overlap(o.start, o.end, lo, hi) for o in ops if o.kernel)


def kernel_by_name(ops: list[Op], lo: float, hi: float) -> dict[str, float]:
    out: dict[str, float] = {}
    for o in ops:
        if o.kernel:
            name = o.instr.rsplit(".", 1)[0] if re.search(
                r"\.\d+$", o.instr) else o.instr
            out[name] = out.get(name, 0.0) + overlap(o.start, o.end, lo, hi)
    return out


def collective_s(ops: list[Op], lo: float, hi: float) -> float:
    return total(union(((o.start, o.end) for o in ops
                        if is_collective(o.opcode)), lo, hi))


def exposed_collective_s(ops: list[Op], lo: float, hi: float) -> float:
    """Seconds in which a collective ran and no other operation did."""
    coll = union(((o.start, o.end) for o in ops if is_collective(o.opcode)),
                 lo, hi)
    compute = union(((o.start, o.end) for o in ops
                     if not is_collective(o.opcode)), lo, hi)
    exposed = 0.0
    j = 0
    for a, b in coll:
        covered = 0.0
        while j < len(compute) and compute[j][1] <= a:
            j += 1
        k = j
        while k < len(compute) and compute[k][0] < b:
            covered += overlap(a, b, *compute[k])
            k += 1
        exposed += (b - a) - covered
    return exposed


def top_ops(ops: list[Op], lo: float, hi: float, k: int = 10):
    """The ``k`` instructions with the most device time in the window."""
    by: dict[str, float] = {}
    for o in ops:
        d = overlap(o.start, o.end, lo, hi)
        if d > 0:
            by[o.instr] = by.get(o.instr, 0.0) + d
    return sorted(by.items(), key=lambda kv: -kv[1])[:k]


def labelled_gaps(ops: list[Op], spans, lo: float, hi: float,
                  k: int = 10, default: str = "program"):
    """The ``k`` longest idle gaps, each named by the host span that
    overlaps it most (``default`` where no benchmark span does: the
    program's own host code, or waiting)."""
    out = []
    for a, b in gaps(union(((o.start, o.end) for o in ops), lo, hi), lo, hi):
        best, label = 0.0, default
        for name, s0, s1 in spans:
            if name == "window":
                continue
            ov = overlap(a, b, s0, s1)
            if ov > best:
                best, label = ov, name
        out.append((label, b - a))
    return sorted(out, key=lambda g: -g[1])[:k]


def mean_over_devices(trace: Trace, fn) -> float | None:
    """Mean of ``fn(ops, lo, hi)`` over the devices in the window."""
    win = trace.window()
    if win is None or not trace.devices:
        return None
    vals = [fn(ops, *win) for ops in trace.devices.values()]
    return sum(vals) / len(vals)


def idle_pct(trace: Trace | None) -> float | None:
    """Percent of the window with no operation on the device, mean over
    the devices."""
    if trace is None:
        return None
    win = trace.window()
    busy = mean_over_devices(trace, busy_s)
    if win is None or busy is None or win[1] <= win[0]:
        return None
    return 100.0 * (1.0 - busy / (win[1] - win[0]))
