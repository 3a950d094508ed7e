"""Device time per update of what lies between the projections into the latent
and the core, in all passes: ``cca_conv`` + ``cca_qk_mean`` +
``cca_value_shift`` + ``cca_norm`` + ``attn_rope`` (small ops bound by
bandwidth, and the rotary kernel)."""
from benchmarks.trace import scopes_zaya


def read(ctx):
    return scopes_zaya.device_ms(ctx, *scopes_zaya.MIX_PARTS)
