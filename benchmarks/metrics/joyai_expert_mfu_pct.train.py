"""The held experts' grouped products' share of the chip's bf16 peak: the
training FLOPs of the slots REALLY routed here in an update (3 x 6 x H x F x
``moe_local_slots``, the program's own counter: gate, up and down product a
slot) over the device time per update under the ``moe_experts`` scope (all
passes, so the piece's re-made forward and the activation between the products
are in the time and not in the FLOPs) and the peak."""
from benchmarks.trace import flops_joyai, scopes_joyai


def read(ctx):
    found = scopes_joyai.for_run(ctx)
    slots = (ctx.get("counters") or {}).get("moe_local_slots")
    if not found or not slots or not found["by_part"].get("moe_experts"):
        return None
    seconds = found["by_part"]["moe_experts"] / ctx["updates"]
    return 100.0 * flops_joyai.routed_expert_train_flops(
        ctx["config"], slots) / (seconds * ctx["chips"] * ctx["peak_flops"])
