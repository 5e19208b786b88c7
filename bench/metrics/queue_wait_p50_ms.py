"""Median of the engine's ``Result.queue_wait_s`` (submit to slot
activation) over the requests activated in the traced window, in
milliseconds."""

import statistics


def read(ctx):
    waits = ctx.work["queue_waits"]
    if not waits:
        return None
    return 1000.0 * statistics.median(waits)
