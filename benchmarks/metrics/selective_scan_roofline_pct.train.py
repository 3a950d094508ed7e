"""The selective scan's share of its roofline over the traced window: the
least time the chip could take for the scan calls the trace holds (the larger
of operations over the bf16 peak and bytes over the HBM peak of
trace/peaks.json; one call's elementwise operations and least bytes from
trace/flops_phi4flash.py ``selective_scan_call``: u, dt, B, C read and y
written once, the backward's likewise; by these two peaks the bytes bound it),
summed, over the device time of the WHOLE ``selective_scan`` scope, kernel or
not. Every call counts, the forward's second run under remat too. The scan is
bound by the vector units, which peaks.json does not list: a low share here
says how far the recurrence is from moving its operands once at HBM speed."""
from benchmarks.trace import flops_phi4flash, scopes_phi4flash


def read(ctx):
    if not ctx.get("config"):
        return None
    return scopes_phi4flash.roofline_pct(
        ctx, flops_phi4flash.SCAN_KERNELS,
        lambda kernel: flops_phi4flash.selective_scan_call(
            ctx["config"], ctx["mix"], kernel),
        spent_s=lambda found: found["by_part"].get("selective_scan", 0.0))
