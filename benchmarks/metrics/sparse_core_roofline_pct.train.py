"""The sparse core's share of its roofline (``sparse_core_device_ms.train``'s
scope): the least time the chip could take for the passes an update makes
(layers x micro-batches x one forward, one recompute under ``--remat full``,
one backward; one pass's operations and least bytes from trace/flops_keye.py
``sparse_core_call``: THE CHOSEN PAIRS of every query head, whatever form the
kernels take, so a mask over dense tiles reads as low as it is; the larger of
operations over the bf16 peak and bytes over the HBM peak of
trace/peaks.json), over that scope's whole device time."""
from benchmarks.trace import flops_keye, scopes_keye


def read(ctx):
    found = scopes_keye.for_run(ctx)
    if not found or not ctx.get("device_kind") or not ctx.get("config"):
        return None
    spent = found["by_part"].get("dsa_core", 0.0)
    if not spent:
        return None
    config, mix = ctx["config"], ctx["mix"]
    least = sum(scopes_keye.least_seconds(
        ctx, *flops_keye.sparse_core_call(config, mix, which))
        for which in flops_keye.PASSES)
    calls = (ctx["updates"] * config["num_hidden_layers"]
             * flops_keye.micro_batches(mix))
    return 100.0 * calls * least / spent
