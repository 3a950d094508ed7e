"""Device time per update of the WINDOWED flash kernels in all passes
(``flash_window_fwd``, ``flash_window_bwd_dq``, ``flash_window_bwd_dkv``: the
sliding layers' attention cores; the forward's second run under remat counts).
A window that is skipped reads below ``full_attention_device_ms.train`` though
the sliding layers have more heads; one that is only masked reads far above."""
from benchmarks.trace import scopes_laguna


def read(ctx):
    return scopes_laguna.device_ms(ctx, "window_attention")
