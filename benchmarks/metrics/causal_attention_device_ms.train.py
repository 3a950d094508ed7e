"""Device time per update of the causal attention core in all passes: the
``attention_core`` scope (the key-value heads' repeat, layout changes, the row
sums round the kernels) and the ``flash_*`` kernels themselves."""
from benchmarks.trace import scopes_lm


def read(ctx):
    return scopes_lm.device_ms(ctx, "attention_core")
