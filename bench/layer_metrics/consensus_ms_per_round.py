"""Device milliseconds under the program's ``rpca.consensus`` scope (the
aggregation of the client factors) per solver round, from the trace of
the window (mean over the devices)."""
from bench import scopes


def read(ctx):
    rounds = ctx.counters.get("rounds", [])
    return scopes.ms_per(ctx, scopes.CONSENSUS, sum(rounds))
