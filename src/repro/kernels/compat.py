"""Interpret-mode resolution shared by every Pallas kernel entry point."""
import os

import jax


def should_interpret(interpret: "bool | None") -> bool:
    """Canonical interpret-mode resolution for EVERY kernel entry point.

    Order: explicit caller arg > ``RPCA_INTERPRET`` env (``1``/``true``/
    ``on`` forces interpret, ``0``/``false``/``off`` forces compiled) >
    backend default (interpret everywhere except real TPU).

    ``interpret`` is a jit ``static_argnames`` participant at every call
    site, so this resolves at trace time: one executable per resolved
    value, and the env override is captured per (shape, static-args)
    trace -- flip it before the first call of a process, not mid-stream.
    """
    if interpret is not None:
        return interpret
    env = os.environ.get("RPCA_INTERPRET", "").strip().lower()
    if env in ("1", "true", "on", "yes"):
        return True
    if env in ("0", "false", "off", "no"):
        return False
    return jax.default_backend() != "tpu"
