"""Seconds from the creation of the process to the trainer's ``main``: the
interpreter, the imports, and what the caller did first (in a cell: the
benchmark's shards, its look for a chip, its probes); ``startup`` record,
``main_entered_s``."""
from benchmarks.trace import startup


def read(ctx):
    return startup.value(ctx, "before_main_s")
