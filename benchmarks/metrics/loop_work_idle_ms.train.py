"""Device idle time per update while the loop does its own work: under
``train:log``, ``train:telemetry``, ``train:checkpoint``, ``train:eval``,
``train:feed``, and under no span at all (``none``: the loop's thread between
two spans, or kept off the processor by another thread)."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.idle_ms(ctx, "train:log", "train:telemetry",
                          "train:checkpoint", "train:eval", "train:feed",
                          "none")
