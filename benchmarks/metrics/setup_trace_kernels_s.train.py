"""Seconds of ``train_step``'s ``trace_s`` building kernels: each
``pallas_call`` site and megablox call reached while tracing, its body traced
and its call bound (``trace_parts.kernels``)."""
from benchmarks.trace import startup_parts


def read(ctx):
    return startup_parts.value(ctx, "trace_kernels_s")
