"""Share of device-busy time that no part rule of trace/scopes_lm.json placed
(ops without an op_name: copies, converts, the loops' own time)."""
from benchmarks.trace import scopes_lm


def read(ctx):
    found = scopes_lm.for_run(ctx)
    if not found or not found["busy_s"]:
        return None
    return 100.0 * found["unattributed_s"] / found["busy_s"]
