"""Seconds tracing, lowering and compiling or loading the init program:
``trace_s`` + ``lower_s`` + ``backend_compile_s`` of the ``init_state``
record, the part of ``startup:state_init`` that is a program."""
from benchmarks.trace import startup_parts


def read(ctx):
    return startup_parts.value(ctx, "init_program_s")
