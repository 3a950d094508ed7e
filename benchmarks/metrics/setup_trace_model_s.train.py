"""Seconds of ``train_step``'s ``trace_s`` in the model's own Python: the flax
modules' self time by class (``trace_parts.modules``), every time the run
traced the step."""
from benchmarks.trace import startup_parts


def read(ctx):
    return startup_parts.value(ctx, "trace_model_s")
