"""The Pallas kernels' share of their roofline, in percent.

The least time of the work that the window's solves required of the
contraction layer (``bench/work.py``: the larger of operations over the
bf16 peak and bytes over HBM bandwidth), over the kernels' device time in
the trace; each device's share of the work against its own kernel time,
averaged over the devices.  Nothing is read where the trace holds no
kernel time.
"""
from bench import peaks, work
from bench import trace_events as te


def read(ctx):
    t, c = ctx.cell.traffic, ctx.cell.config
    rounds = ctx.counters.get("rounds", [])
    win = ctx.trace.window() if ctx.trace is not None else None
    if not rounds or win is None or not ctx.trace.devices:
        return None
    pk = peaks.peaks_for(ctx.device_kind)
    a = t["algorithm"]
    blocks = t.get("clients") or 1
    total = work.Work(0.0, 0.0)
    for r in rounds:
        total = total + work.solve_work(
            c["m"], c["n"], c["rank"], blocks=blocks, rounds=r,
            local_iters=a["local_iters"], inner_sweeps=a["inner_sweeps"],
            masked=t.get("observed", 1.0) < 1.0)
    per_device = total.scale(1.0 / len(ctx.trace.devices))
    least, _ = work.least_time(per_device, pk.flops_bf16, pk.hbm_bw)
    shares = []
    for ops in ctx.trace.devices.values():
        k = te.kernel_s(ops, *win)
        if k <= 0:
            return None
        shares.append(100.0 * least / k)
    return sum(shares) / len(shares)
