"""The differential flash kernels' share of their roofline over the traced
window, every call, band or triangle: for each of the nine kernels
(``flash_diff_window_*``, ``flash_diff_*``, ``flash_diff_cross_*``) the least
time the chip could take for the calls the trace holds (the larger of FLOPs
over the bf16 peak and bytes over the HBM peak of trace/peaks.json; FLOPs and
bytes of one call from trace/flops_phi4flash.py ``flash_diff_call``: keys of 64
and values of 128, the pairs INSIDE THE BAND on a sliding layer and the
triangle's on a full or cross one), summed, over those kernels' device time.
The forward's second run under remat counts: it is work the kernel did."""
from benchmarks.trace import flops_phi4flash, scopes_phi4flash


def read(ctx):
    if not ctx.get("config"):
        return None
    return scopes_phi4flash.roofline_pct(
        ctx, flops_phi4flash.DIFF_KERNELS,
        lambda kernel: flops_phi4flash.flash_diff_call(
            ctx["config"], ctx["mix"], kernel))
