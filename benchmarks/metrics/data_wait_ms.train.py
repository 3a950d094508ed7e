"""Median time the trainer's loop waits for its next batch (loader + device
prefetch), from the feed probe's host clock, over the window's updates."""
import statistics


def read(ctx):
    waits = ctx.get("data_wait_s")
    return 1e3 * statistics.median(waits) if waits else None
