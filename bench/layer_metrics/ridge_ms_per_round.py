"""Device milliseconds under the program's ``rpca.ridge`` scope (the r x r
Grams, Cholesky factorizations and triangular solves of a round) per
solver round, from the trace of the window (mean over the devices)."""
from bench import scopes


def read(ctx):
    rounds = ctx.counters.get("rounds", [])
    return scopes.ms_per(ctx, scopes.RIDGE, sum(rounds))
