"""Device time per update, mean over the chips, of the causal flash kernels
without a window in all passes (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``: the full layer's attention core over each chip's own rows;
the forward's second run under remat counts):
``full_attention_device_ms.train`` for the family under an expert axis."""
from benchmarks.trace import scopes_mellum


def read(ctx):
    return scopes_mellum.device_ms(ctx, "full_attention")
