"""Device time per update of the router in all passes: everything under
``moe_route`` (``router_down``, ``router_eda``, ``router_mlp``, and the
softmax, the choice and the chosen probability)."""
from benchmarks.trace import scopes_zaya


def read(ctx):
    return scopes_zaya.device_ms(ctx, "router_down", "router_eda", "router_mlp",
                                 "moe_route")
