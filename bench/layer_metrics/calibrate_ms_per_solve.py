"""Device milliseconds under the program's ``rpca.calibrate`` scope (the
threshold's medians, ``factorized.robust_lam``) per solve, from the trace
of the window (mean over the devices)."""
from bench import scopes


def read(ctx):
    rounds = ctx.counters.get("rounds", [])
    return scopes.ms_per(ctx, scopes.CALIBRATE, len(rounds))
