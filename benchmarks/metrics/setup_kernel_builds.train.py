"""How many kernel builds tracing ``train_step`` made: one a ``pallas_call``
site or megablox call reached. Against the kernel calls an update makes on
the device it says which kernels share a trace (a jitted entry point) and
which are traced anew at every call."""
from benchmarks.trace import startup_parts


def read(ctx):
    return startup_parts.value(ctx, "kernel_builds")
