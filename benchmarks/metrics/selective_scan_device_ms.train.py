"""Device time per update of the selective scan in all passes: the scope
``selective_scan`` whole, kernel or not (the kernels ``selective_scan_fwd`` /
``selective_scan_bwd`` and what XLA does round them: the casts, B and C spread
over a tile of lanes, the sums of the cotangents' pieces; the forward's second
run under remat counts)."""
from benchmarks.trace import scopes_phi4flash


def read(ctx):
    return scopes_phi4flash.device_ms(ctx, "selective_scan")
