"""The experts' grouped products' share of the chips' bf16 peak: the training
FLOPs of the slots that ARRIVED at their experts in an update (3 x 6 x H x F
x ``moe_local_slots``, the program's own counter summed over the chips: gate,
up and down product a slot) over the device time per update under the
``moe_experts`` scope (mean over the chips, all passes, so the round's
re-made forward and the activation between the products are in the time and
not in the FLOPs), the chips and the peak."""
from benchmarks.trace import flops_mellum, scopes_mellum


def read(ctx):
    found = scopes_mellum.for_run(ctx)
    slots = (ctx.get("counters") or {}).get("moe_local_slots")
    if not found or not slots or not found["by_part"].get("moe_experts"):
        return None
    seconds = found["by_part"]["moe_experts"] / ctx["updates"]
    return 100.0 * flops_mellum.routed_expert_train_flops(
        ctx["config"], slots) / (seconds * ctx["chips"] * ctx["peak_flops"])
