"""Device time per update of the six layers' gated MLPs in all passes (the
``dense_mlp`` scope: ``fc1``, ``silu(g) * u``, ``fc2``)."""
from benchmarks.trace import scopes_phi4flash


def read(ctx):
    return scopes_phi4flash.device_ms(ctx, "dense_mlp")
