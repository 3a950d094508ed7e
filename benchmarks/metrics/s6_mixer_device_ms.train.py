"""Device time per update of the Mamba-1 mixers in all passes: the scope
``s6_mixer`` whole (``s6_in_proj``, ``s6_conv``, ``s6_dt``, ``selective_scan``,
``s6_gate``, ``s6_out_proj`` and what else runs under it)."""
from benchmarks.trace import scopes_phi4flash


def read(ctx):
    return scopes_phi4flash.device_ms(
        ctx, "s6_in_proj", "s6_conv", "s6_dt", "selective_scan", "s6_gate",
        "s6_out_proj", "s6_other")
