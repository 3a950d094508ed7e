"""Seconds in ``main``'s preparation: the ``startup:*`` spans but
``startup:state_init`` (arguments to mesh with the backend's first touch,
model, optimizer, dataset and loader, step builders and telemetry)."""
from benchmarks.trace import startup


def read(ctx):
    return startup.value(ctx, "prepare_s")
