"""Device time per update of the gated attention layer outside its core, in
all passes: ``attn_qkv`` (q with its gate, k, v), ``attn_qk_norm``,
``attn_rope`` (the tables and the turn), ``attn_gate`` and ``attn_out``."""
from benchmarks.trace import scopes_qwen3next


def read(ctx):
    return scopes_qwen3next.device_ms(
        ctx, *scopes_qwen3next.ATTENTION_PROJ_PARTS)
