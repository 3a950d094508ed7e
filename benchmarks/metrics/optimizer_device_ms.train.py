"""Device time per update under the step's ``optimizer`` scope (the division
by the micro-batch count, clipping, LAMB, apply_updates)."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.device_ms(ctx, "by_pass", "optimizer")
