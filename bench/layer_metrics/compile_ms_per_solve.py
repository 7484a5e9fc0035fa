"""Milliseconds of tracing, lowering, compiling and compile-cache loading
that JAX reported inside the window, per solve (front door)."""


def read(ctx):
    c = ctx.counters
    if not c.get("solves"):
        return None
    return c["compile_s"] * 1e3 / c["solves"]
