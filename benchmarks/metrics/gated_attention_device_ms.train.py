"""Device time per update of the gated attention layer's core in all passes:
the ``flash_gated_*`` kernels and what else runs under ``attention_core`` (the
key-value heads' repeat, layout changes, the row sums round the kernels)."""
from benchmarks.trace import scopes_qwen3next


def read(ctx):
    return scopes_qwen3next.device_ms(ctx, "flash_gated", "attention_core")
