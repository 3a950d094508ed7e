"""Device time per update of the delta-rule mixers, in all passes: everything
under the ``gdn`` scope (both input projections, the convolution, beta, g and
the l2 norms, the rule, the gated norm, the output projection)."""
from benchmarks.trace import scopes_qwen3next


def read(ctx):
    return scopes_qwen3next.device_ms(ctx, *scopes_qwen3next.GDN_PARTS)
