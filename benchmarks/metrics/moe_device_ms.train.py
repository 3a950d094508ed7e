"""Device time per update of the expert layers in all passes: everything
under the ``moe`` scope (router, dispatch, the held experts' grouped products,
combine, the shared expert)."""
from benchmarks.trace import scopes_lm


def read(ctx):
    return scopes_lm.device_ms(ctx, "moe_route", "moe_dispatch", "moe_experts",
                               "moe_combine", "moe_shared", "moe_other")
