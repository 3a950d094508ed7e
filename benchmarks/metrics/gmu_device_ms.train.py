"""Device time per update of the gated memory units in all passes (the
``gmu`` scope: two projections and the gate on the kept memory)."""
from benchmarks.trace import scopes_phi4flash


def read(ctx):
    return scopes_phi4flash.device_ms(ctx, "gmu")
