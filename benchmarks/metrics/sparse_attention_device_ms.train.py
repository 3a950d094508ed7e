"""Device time per update of the sparse attention, in all passes: everything
under the ``dsa`` scope (the indexer's projections, its scores, the choice,
the core over the chosen keys, the indexer's KL)."""
from benchmarks.trace import scopes_keye


def read(ctx):
    return scopes_keye.device_ms(ctx, *scopes_keye.DSA_PARTS)
