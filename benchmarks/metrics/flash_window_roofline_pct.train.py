"""The windowed flash kernels' share of their roofline over the traced window:
for each of ``flash_window_fwd``, ``flash_window_bwd_dq``,
``flash_window_bwd_dkv`` the least time the chip could take for the calls the
trace holds (the larger of FLOPs over the bf16 peak and bytes over the HBM peak
of trace/peaks.json; FLOPs and bytes of one call from trace/flops_laguna.py:
the pairs INSIDE THE BAND, window x S less the rows' short starts, not the
triangle's), summed, over those kernels' device time. Every call counts, the
forward's second run under remat too: it is work the kernel did. The tiles the
band's edges cross are computed whole and masked, so a window of one tile reads
at most about half."""
from benchmarks.trace import flops, flops_laguna, scopes_laguna


def read(ctx):
    found = scopes_laguna.for_run(ctx)
    if not found or not ctx.get("device_kind"):
        return None
    calls = {k: n for k, n in found["kernel_calls"].items()
             if k in flops_laguna.WINDOW_KERNELS}
    spent = sum(found["kernels"].get(k, 0.0) for k in calls)
    if not spent:
        return None
    peaks = flops.peaks(ctx["device_kind"])
    least = 0.0
    for kernel, n in calls.items():
        work, traffic = flops_laguna.flash_window_call(
            ctx["config"], ctx["mix"], kernel)
        least += n * max(work / peaks["bf16_flops_per_s"],
                         traffic / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
