"""Device time per update of the latent attention's core in all passes: the
``flash_cca_*`` kernels and what else runs under ``attention_core`` (the
key-value heads' repeat, layout changes, the row sums round the kernels)."""
from benchmarks.trace import scopes_zaya


def read(ctx):
    return scopes_zaya.device_ms(ctx, "flash_cca", "attention_core")
