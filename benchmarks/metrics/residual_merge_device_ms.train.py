"""Device time per update of the learned merges in all passes (scope
``residual_merge``: two a layer, four vectors each, the stream read and
written in float32)."""
from benchmarks.trace import scopes_zaya


def read(ctx):
    return scopes_zaya.device_ms(ctx, "residual_merge")
