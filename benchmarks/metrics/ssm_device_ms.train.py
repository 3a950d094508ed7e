"""Device time per update of the Mamba-2 mixers in all passes: everything
under the ``ssm_mixer`` scope (projections, convolution, the chunked scan,
the gated norm), by trace/scopes_lm.json's part rules."""
from benchmarks.trace import scopes_lm


def read(ctx):
    return scopes_lm.device_ms(ctx, "ssd_scan", "ssm_in_proj", "ssm_conv",
                               "ssm_gate_norm", "ssm_out_proj", "ssm_other")
