"""Share of device-busy time that no part rule of trace/scopes_mellum.json
placed (ops without an op_name that are no copy or convert: the loops' own
time)."""
from benchmarks.trace import scopes_mellum


def read(ctx):
    found = scopes_mellum.for_run(ctx)
    if not found or not found["busy_s"]:
        return None
    return 100.0 * found["unattributed_s"] / found["busy_s"]
