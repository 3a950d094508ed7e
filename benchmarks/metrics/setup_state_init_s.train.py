"""Seconds in ``startup:state_init``: shardings, the init program built or
loaded and run, a checkpoint restored (``startup:restore``), up to the fetch
of the optimizer's count."""
from benchmarks.trace import startup


def read(ctx):
    return startup.value(ctx, "state_init_s")
