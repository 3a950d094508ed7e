"""Device time per update of the causal flash kernels without a window in all
passes (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``: the full layers'
attention cores; the forward's second run under remat counts)."""
from benchmarks.trace import scopes_laguna


def read(ctx):
    return scopes_laguna.device_ms(ctx, "full_attention")
