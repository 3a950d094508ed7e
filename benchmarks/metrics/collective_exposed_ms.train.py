"""Per update, the time collective ops run on a chip while no other op does
(mean over chips), from the trace. Nothing to read on one chip."""


def read(ctx):
    summary, updates = ctx.get("summary"), ctx.get("updates")
    if not summary or not updates or ctx.get("chips", 1) < 2:
        return None
    return 1e3 * summary["collective_exposed_s"] / updates
