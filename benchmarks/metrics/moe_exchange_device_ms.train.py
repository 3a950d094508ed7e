"""Device time per update of the exchange between chips, in all passes, mean
over the chips: the union of every op under ``moe_exchange_out`` (counts,
rows and the backward pass's cotangents on their way to the experts) or
``moe_exchange_back`` (terms, and the rows' and weights' cotangents,
returning), collectives in flight counted (``trace/scopes_mellum.py
exchange_times``). Nothing to read without those scopes."""
from benchmarks.trace import scopes_mellum


def read(ctx):
    found = scopes_mellum.for_run(ctx)
    if not found or not found["exchange"].get("exchange_s"):
        return None
    return 1e3 * found["exchange"]["exchange_s"] / ctx["updates"]
