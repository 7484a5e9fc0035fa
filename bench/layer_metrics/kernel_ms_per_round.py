"""Device milliseconds of the Pallas kernels per solver round, from the
trace of the window (mean over the devices)."""
from bench import trace_events as te


def read(ctx):
    rounds = sum(ctx.counters.get("rounds", []))
    if ctx.trace is None or not rounds:
        return None
    k = te.mean_over_devices(ctx.trace, te.kernel_s)
    return k * 1e3 / rounds if k else None
