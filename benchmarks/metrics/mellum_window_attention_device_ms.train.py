"""Device time per update, mean over the chips, of the WINDOWED flash kernels
in all passes (``flash_window_fwd``, ``flash_window_bwd_dq``,
``flash_window_bwd_dkv``: the three sliding layers' attention cores over each
chip's own rows; the forward's second run under remat counts):
``window_attention_device_ms.train`` for the family under an expert axis."""
from benchmarks.trace import scopes_mellum


def read(ctx):
    return scopes_mellum.device_ms(ctx, "window_attention")
