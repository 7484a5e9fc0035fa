"""Percent of the traced window in which no operation ran on the device
(mean over the devices), in the solve cells."""
from bench import trace_events as te


def read(ctx):
    return te.idle_pct(ctx.trace)
