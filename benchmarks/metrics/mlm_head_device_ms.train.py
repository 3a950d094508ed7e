"""Device time per update of the prediction head (gather of the masked
positions, transform, decoder), the pooler and NSP head, and the
``mlm_loss`` / ``nsp_loss`` scopes, in all passes."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.device_ms(ctx, "by_part", "mlm_head")
