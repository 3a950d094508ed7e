"""Device time per update of the choice itself, in all passes: everything
under the ``dsa_select`` scope (the k-th largest score of every query's row
by counting passes, the tie rule, the mask)."""
from benchmarks.trace import scopes_keye


def read(ctx):
    return scopes_keye.device_ms(ctx, "dsa_select")
