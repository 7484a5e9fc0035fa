"""Percent of the window's device-busy time spent in operations under no
``rpca.*`` scope of the program (start, finalize, carry copies): what the
scoped metrics cannot attribute (mean over the devices)."""
from bench import scopes


def read(ctx):
    return scopes.unscoped_pct(ctx)
