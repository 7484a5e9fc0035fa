"""Milliseconds of the window per solver round: the window's length over
the rounds of its solves (host clock)."""


def read(ctx):
    c = ctx.counters
    rounds = sum(c.get("rounds", []))
    return c["window_s"] * 1e3 / rounds if rounds else None
