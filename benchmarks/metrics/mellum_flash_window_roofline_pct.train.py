"""The windowed flash kernels' share of their roofline over the traced window
(``scopes_mellum.kernels_roofline_pct``; FLOPs and bytes of one call from
``flops_mellum.flash_call``: the pairs INSIDE THE BAND, window x S less the
rows' short starts, 32 heads on a chip's one row of a micro-batch). The tiles
the band's edges cross are computed whole and masked, so a window of one tile
reads at most about half. ``flash_window_roofline_pct.train`` for the family
under an expert axis."""
from benchmarks.trace import flops_mellum, scopes_mellum
from benchmarks.trace.flops_laguna import WINDOW_KERNELS


def read(ctx):
    return scopes_mellum.kernels_roofline_pct(
        ctx, WINDOW_KERNELS, flops_mellum.flash_call)
