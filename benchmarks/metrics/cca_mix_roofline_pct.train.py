"""The share of their roofline of the ops between the projections into the
latent and the core (``cca_mix_device_ms.train``'s scopes): the least time the
chip could take for the passes an update makes (layers x micro-batches x one
forward, one recompute under ``--remat full``, one backward; one pass's
operations and least bytes from trace/flops_zaya.py ``cca_mix_call``: z and v
read, q, k and v written once; the larger of operations over the bf16 peak and
bytes over the HBM peak of trace/peaks.json: the bytes bound it), over those
scopes' device time. A kernel that takes their place is read by the same
scopes."""
from benchmarks.trace import flops_zaya, scopes_zaya


def read(ctx):
    found = scopes_zaya.for_run(ctx)
    if not found or not ctx.get("device_kind") or not ctx.get("config"):
        return None
    spent = sum(found["by_part"].get(p, 0.0) for p in scopes_zaya.MIX_PARTS)
    if not spent:
        return None
    config, mix = ctx["config"], ctx["mix"]
    least = sum(scopes_zaya.least_seconds(
        ctx, *flops_zaya.cca_mix_call(config, mix, which))
        for which in flops_zaya.MIX_PASSES)
    calls = (ctx["updates"] * config["num_hidden_layers"]
             * flops_zaya.micro_batches(mix))
    return 100.0 * calls * least / spent
