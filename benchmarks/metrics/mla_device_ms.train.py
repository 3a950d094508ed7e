"""Device time per update of the latent attentions, in all passes: everything
under the ``mla`` scope (both query products and the latent norm, the keys'
and values' down, norm and up, the interleaved turn, the core with its built
key, the output projection) and the rotary tables, in the layers and in the
multi-token-prediction module's block alike."""
from benchmarks.trace import scopes_joyai


def read(ctx):
    return scopes_joyai.device_ms(ctx, *scopes_joyai.MLA_PARTS)
