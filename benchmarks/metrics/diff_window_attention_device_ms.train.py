"""Device time per update of the WINDOWED differential flash kernels in all
passes (``flash_diff_window_fwd``, ``..._bwd_dq``, ``..._bwd_dkv``: the sliding
layers' attention cores, both softmax maps of every pair in one call; the
forward's second run under remat counts)."""
from benchmarks.trace import scopes_phi4flash


def read(ctx):
    return scopes_phi4flash.device_ms(ctx, "diff_window_attention")
