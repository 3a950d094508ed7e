"""Device-busy time per optimizer update, from the profiler trace: the union
of op intervals (mean over chips) over the traced updates."""


def read(ctx):
    summary, updates = ctx.get("summary"), ctx.get("updates")
    if not summary or not updates:
        return None
    return 1e3 * summary["busy_s"] / updates
