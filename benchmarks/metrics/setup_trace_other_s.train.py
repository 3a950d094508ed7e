"""Seconds of ``train_step``'s ``trace_s`` in neither modules nor kernel
builds: the optimizer's update over its leaves (``optimizer_s``) and JAX's own
passes with the step's Python round the model (``other_s``); the
``startup_parts:`` line prints the two apart."""
from benchmarks.trace import startup_parts


def read(ctx):
    return startup_parts.value(ctx, "trace_other_s")
