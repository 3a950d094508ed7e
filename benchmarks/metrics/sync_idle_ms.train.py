"""Device idle time per update while the loop waits for the device:
under ``train:sync`` (the telemetry cadence's block_until_ready) and
``train:fetch_metrics`` (the ``float(v)`` fetches of a logged step). What is
idle there is the way back from the device to the host."""
from benchmarks.trace import scopes


def read(ctx):
    return scopes.idle_ms(ctx, "train:sync", "train:fetch_metrics")
