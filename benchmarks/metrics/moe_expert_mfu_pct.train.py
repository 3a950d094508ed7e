"""The held experts' grouped products' share of the chip's bf16 peak: the
training FLOPs of the slots REALLY routed here in an update (3 x 4 x H x F x
``moe_local_slots``, the program's own counter) over the device time per update
under the ``moe_experts`` scope (all passes, so the forward's recomputation
under remat and the activation between the two products are in the time and
not in the FLOPs) and the peak. A product that follows a worst-case capacity
reads low here."""
from benchmarks.trace import flops_lm, scopes_lm


def read(ctx):
    found = scopes_lm.for_run(ctx)
    slots = (ctx.get("counters") or {}).get("moe_local_slots")
    if not found or not slots or not found["by_part"].get("moe_experts"):
        return None
    seconds = found["by_part"]["moe_experts"] / ctx["updates"]
    return 100.0 * flops_lm.routed_expert_train_flops(ctx["config"], slots) / (
        seconds * ctx["chips"] * ctx["peak_flops"])
