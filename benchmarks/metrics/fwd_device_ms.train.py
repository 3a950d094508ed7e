"""Device time per update of the forward pass: ops whose op_name carries
``jvp(`` and neither ``transpose(`` nor ``rematted_computation`` (nor the
``optimizer`` scope), by trace/scopes.py's pass rules."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.device_ms(ctx, "by_pass", "forward")
