"""Device time per update of attention outside its core, in all passes: the
q, k, v projections (``attn_qkv``), the rotary tables and turns
(``attn_rope``), the per-head gate (``attn_gate``: its projection, sigmoid and
product) and the output projection (``attn_out``)."""
from benchmarks.trace import scopes_laguna


def read(ctx):
    return scopes_laguna.device_ms(ctx, "attn_qkv", "attn_rope", "attn_gate",
                                   "attn_out")
