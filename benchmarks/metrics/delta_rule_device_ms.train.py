"""Device time per update of the gated delta rule itself in all passes:
everything under the ``delta_rule`` scope (the chunks' products, the
triangular inverse, the scan over chunks, the layout changes round them; a
kernel's calls too if one is built)."""
from benchmarks.trace import scopes_qwen3next


def read(ctx):
    return scopes_qwen3next.device_ms(ctx, "delta_rule")
