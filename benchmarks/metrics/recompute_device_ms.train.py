"""Device time per update spent computing the forward again inside the
backward (``rematted_computation``: what ``remat dots`` does not keep), by
trace/scopes.py's pass rules."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.device_ms(ctx, "by_pass", "recompute")
