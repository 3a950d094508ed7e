"""Of ``moe_exchange_device_ms.train``, the time per update during which no
other op runs on that chip (mean over the chips): what the exchange adds to
the update where none of it hides behind compute."""
from benchmarks.trace import scopes_mellum


def read(ctx):
    found = scopes_mellum.for_run(ctx)
    if not found or not found["exchange"].get("exchange_s"):
        return None
    return 1e3 * found["exchange"]["exposed_s"] / ctx["updates"]
