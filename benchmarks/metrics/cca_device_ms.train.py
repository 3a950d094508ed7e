"""Device time per update of the attention inside the latent, in all passes:
everything under the ``cca`` scope (projections into the latent and back, both
convolutions, q-k mean, norm, value shift, rotary turn, the core and its
kernels)."""
from benchmarks.trace import scopes_zaya


def read(ctx):
    return scopes_zaya.device_ms(ctx, *scopes_zaya.CCA_PARTS)
