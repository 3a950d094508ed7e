"""Seconds tracing and lowering ``train_step``, every time the run did so:
``trace_s`` + ``lower_s`` of its ``compile`` records, and its cost record's
``analysis_s`` (the second lowering, the text and cost queries) less the
compile-or-load inside it."""
from benchmarks.trace import startup


def read(ctx):
    return startup.value(ctx, "step_lower_s")
