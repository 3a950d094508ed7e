"""Device time per update of the core over the chosen keys, in all passes:
everything under the ``dsa_core`` scope (a kernel's calls, the layout changes
round them, or the XLA form's blocks)."""
from benchmarks.trace import scopes_keye


def read(ctx):
    return scopes_keye.device_ms(ctx, "dsa_core")
