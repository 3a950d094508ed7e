"""Device time per update of what in the expert layers is not a matmul of an
expert: ``moe_route`` (router, top-k) + ``moe_dispatch`` (sort, gather) +
``moe_combine`` (weights, scatter-add), in all passes."""
from benchmarks.trace import scopes_lm


def read(ctx):
    return scopes_lm.device_ms(ctx, "moe_route", "moe_dispatch", "moe_combine")
