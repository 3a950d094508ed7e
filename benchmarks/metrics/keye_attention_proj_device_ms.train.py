"""Device time per update of the attention layer outside the ``dsa`` scope, in
all passes: ``attn_qkv``, ``attn_qk_norm``, ``attn_rope`` (the tables and the
turn) and ``attn_out``."""
from benchmarks.trace import scopes_keye


def read(ctx):
    return scopes_keye.device_ms(ctx, *scopes_keye.ATTENTION_PROJ_PARTS)
