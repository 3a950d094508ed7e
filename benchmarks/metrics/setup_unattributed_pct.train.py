"""Share of the time from the process's creation to the first update that no
span of the start-up and no part of the first update covers
(``unattributed_s`` / ``time_to_first_update_s``)."""
from benchmarks.trace import startup


def read(ctx):
    return startup.value(ctx, "unattributed_pct")
