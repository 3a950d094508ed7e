"""Seconds of the first update (its batch's wait, its step call, its device
sync) that are neither lowering nor the executable: dispatch, the first
execution, and what a wrapper of the step does inside the call."""
from benchmarks.trace import startup


def read(ctx):
    return startup.value(ctx, "first_update_s")
