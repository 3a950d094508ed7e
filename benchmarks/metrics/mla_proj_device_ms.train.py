"""Device time per update of the latent attentions' projections, in all
passes: ``mla_q_proj`` (down, the latent norm, up), ``mla_kv_proj`` (down,
norm, up) and ``attn_out``."""
from benchmarks.trace import scopes_joyai


def read(ctx):
    return scopes_joyai.device_ms(ctx, *scopes_joyai.PROJ_PARTS)
