"""Device time per update of the FULL-CAUSAL differential flash kernels in
all passes: the layer that writes the kept keys and values (``flash_diff_fwd``,
``..._bwd_dq``, ``..._bwd_dkv``) and the cross layers that read them
(``flash_diff_cross_*``)."""
from benchmarks.trace import scopes_phi4flash


def read(ctx):
    return scopes_phi4flash.device_ms(
        ctx, "diff_full_attention", "diff_cross_attention")
