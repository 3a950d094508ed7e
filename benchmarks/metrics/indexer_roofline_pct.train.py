"""The indexer's scoring's share of its roofline: the least time the chip
could take for the passes an update makes (layers x micro-batches x one
forward and one recompute under ``--remat full`` of the CAUSAL pairs' scores,
one backward of the KL through the CHOSEN pairs' scores; one pass's
operations and least bytes from trace/flops_keye.py ``indexer_call``; the
larger of operations over the bf16 peak and bytes over the HBM peak of
trace/peaks.json), over the device time of EVERY scope that scoring can run
under: ``dsa_scores``, ``dsa_select`` (the kernel path scores inside the
choice's kernel and leaves ``dsa_scores`` empty) and ``dsa_index_loss``. So
scoring moved from one scope to another moves nothing here. The choice's
counting passes, the second scoring and the rebuilt probabilities the KL
needs are in the time and not in the operations."""
from benchmarks.trace import flops_keye, scopes_keye


def read(ctx):
    found = scopes_keye.for_run(ctx)
    if not found or not ctx.get("device_kind") or not ctx.get("config"):
        return None
    spent = sum(found["by_part"].get(p, 0.0)
                for p in ("dsa_scores", "dsa_select", "dsa_index_loss"))
    if not spent:
        return None
    config, mix = ctx["config"], ctx["mix"]
    least = sum(scopes_keye.least_seconds(
        ctx, *flops_keye.indexer_call(config, mix, which))
        for which in flops_keye.PASSES)
    calls = (ctx["updates"] * config["num_hidden_layers"]
             * flops_keye.micro_batches(mix))
    return 100.0 * calls * least / spent
