"""Device time per update of attention, in all passes, mean over the chips:
the q, k, v projections (``attn_qkv``), the rotary tables and turns
(``attn_rope``), both cores (the windowed kernels of the sliding layers, the
causal ones of the full layer, and what else runs under ``attention_core``)
and the output projection (``attn_out``)."""
from benchmarks.trace import scopes_mellum


def read(ctx):
    return scopes_mellum.device_ms(
        ctx, "attn_qkv", "attn_rope", "window_attention", "full_attention",
        "attention_core", "attn_out")
