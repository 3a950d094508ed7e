"""Device time per update of the chunked scan alone in all passes (the
``ssd_scan`` scope: the recurrence without its projections)."""
from benchmarks.trace import scopes_lm


def read(ctx):
    return scopes_lm.device_ms(ctx, "ssd_scan")
