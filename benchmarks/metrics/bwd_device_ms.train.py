"""Device time per update of the backward pass: ops under ``transpose(`` that
are not recomputation, by trace/scopes.py's pass rules."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.device_ms(ctx, "by_pass", "backward")
