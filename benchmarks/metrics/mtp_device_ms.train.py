"""Device time per update of the multi-token-prediction module as a whole, in
all passes: everything under the ``mtp`` scope (its two norms and ``W_eh``,
its block's latent attention and expert layer, its final norm) and its pass
of the shared head with its loss (``mtp_head``, ``mtp_loss``). Read in a
second reduction of the trace (trace/scopes_joyai.py), so the block's parts
are ALSO in ``mla_device_ms.train`` and ``moe_device_ms.train``."""
from benchmarks.trace import scopes_joyai


def read(ctx):
    found = scopes_joyai.for_run(ctx)
    if not found:
        return None
    return 1e3 * found["module_s"] / ctx["updates"]
