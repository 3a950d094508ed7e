"""Share of the traced window in which no op ran on the chip (mean over
chips): 1 - busy / window."""


def read(ctx):
    summary = ctx.get("summary")
    if not summary or not summary["window_s"]:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
