"""Device time per update of the latent attentions' core, in all passes: the
``mla_core`` scope (the key built from ``k_nope`` and the shared turned key,
the three flash kernels' calls, the layout changes round them)."""
from benchmarks.trace import scopes_joyai


def read(ctx):
    return scopes_joyai.device_ms(ctx, "mla_core")
