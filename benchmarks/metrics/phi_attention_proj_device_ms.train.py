"""Device time per update of what stands round the differential attention
cores in all passes: ``attn_qkv`` (``Wqkv`` or ``Wq`` with its bias),
``attn_diff`` (the lambda, the subtraction, the 128-wide norm) and ``attn_out``
(``out_proj`` with its bias)."""
from benchmarks.trace import scopes_phi4flash


def read(ctx):
    return scopes_phi4flash.device_ms(ctx, "attn_qkv", "attn_diff", "attn_out")
