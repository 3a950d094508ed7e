"""Device time per update of the dense gated MLP (the ``dense_mlp`` scope:
three products of width ``intermediate_size``) in all passes."""
from benchmarks.trace import scopes_laguna


def read(ctx):
    return scopes_laguna.device_ms(ctx, "dense_mlp")
