"""Mean solver rounds of the window's solves (``SolveStats.rounds``)."""


def read(ctx):
    r = ctx.counters.get("rounds")
    return sum(r) / len(r) if r else None
