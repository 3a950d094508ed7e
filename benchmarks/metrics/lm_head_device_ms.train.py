"""Device time per update of the output head and the next-token loss in all
passes (the ``lm_head`` and ``lm_loss`` scopes; taken in pieces of the
sequence, each rematerialized)."""
from benchmarks.trace import scopes_lm


def read(ctx):
    return scopes_lm.device_ms(ctx, "lm_head", "lm_loss")
