"""Device milliseconds under the program's ``rpca.round`` scope per solver
round, from the trace of the window (mean over the devices): the round's
own time, without the calibration that ``round_ms`` charges to it."""
from bench import scopes


def read(ctx):
    rounds = ctx.counters.get("rounds", [])
    return scopes.ms_per(ctx, scopes.ROUND, sum(rounds))
