"""The exchange's share of its roofline, the links out of a chip: the bytes
the slots REALLY sent to other chips make in an update (``flops_mellum.
exchange_bytes_per_update`` of ``moe_exchange_slots_out``, the program's
counter: seven crossings of a row a remote slot under ``--remat full``), a
chip's share of them, over ``ici_bits_per_s / 8`` (``trace/peaks.json``) and
over the exchange's device time per update (``moe_exchange_device_ms.train``:
collectives in flight counted, the places of a round that carry no slot and
the waits for the slowest chip in the time and not in the bytes)."""
from benchmarks.trace import flops, flops_mellum, scopes_mellum


def read(ctx):
    found = scopes_mellum.for_run(ctx)
    slots = (ctx.get("counters") or {}).get("moe_exchange_slots_out")
    if not found or not slots or not found["exchange"].get("exchange_s"):
        return None
    seconds = found["exchange"]["exchange_s"] / ctx["updates"]
    per_chip = flops_mellum.exchange_bytes_per_update(
        ctx["config"], slots) / ctx["chips"]
    peak = flops.peaks(ctx["device_kind"])["ici_bits_per_s"] / 8.0
    return 100.0 * per_chip / (seconds * peak)
