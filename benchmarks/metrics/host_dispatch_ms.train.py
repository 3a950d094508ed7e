"""Median host time inside one call of the trainer's step (dispatch, not the
device's work), from the step probe's host clock."""
import statistics


def read(ctx):
    calls = ctx.get("dispatch_s")
    return 1e3 * statistics.median(calls) if calls else None
