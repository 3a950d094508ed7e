"""The gated delta rule's share of its roofline (``delta_rule_device_ms.train``'s
scope): the least time the chip could take for the passes an update makes
(delta-rule layers x micro-batches x one forward, one recompute under
``--remat full``, one backward; one pass's operations and least bytes from
trace/flops_qwen3next.py ``delta_rule_call``: the chunked rule's products at
chunk 64, q, k, v, g, beta read and o written once; the larger of operations
over the bf16 peak and bytes over the HBM peak of trace/peaks.json), over that
scope's whole device time. A kernel that takes the scope's place is read by
the same scope."""
from benchmarks.trace import flops_qwen3next, scopes_qwen3next


def read(ctx):
    found = scopes_qwen3next.for_run(ctx)
    if not found or not ctx.get("device_kind") or not ctx.get("config"):
        return None
    spent = found["by_part"].get("delta_rule", 0.0)
    if not spent:
        return None
    config, mix = ctx["config"], ctx["mix"]
    least = sum(scopes_qwen3next.least_seconds(
        ctx, *flops_qwen3next.delta_rule_call(config, mix, which))
        for which in flops_qwen3next.RULE_PASSES)
    layers = flops_qwen3next.layer_kinds(config).count("linear_attention")
    calls = ctx["updates"] * layers * flops_qwen3next.micro_batches(mix)
    return 100.0 * calls * least / spent
