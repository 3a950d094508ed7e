"""The causal flash kernels' share of their roofline over the traced window
(``scopes_mellum.kernels_roofline_pct``; FLOPs and bytes of one call from
``flops_mellum.flash_call``: the CAUSAL HALF of the square, S (S + 1) / 2
pairs a head, which the tiles a skipping kernel visits round up to): the full
layer's core. ``flash_causal_roofline_pct.train`` for the family under an
expert axis."""
from benchmarks.trace import flops_mellum, scopes_mellum
from benchmarks.trace.flops_lm import FLASH_MATMULS


def read(ctx):
    return scopes_mellum.kernels_roofline_pct(
        ctx, FLASH_MATMULS, flops_mellum.flash_call)
