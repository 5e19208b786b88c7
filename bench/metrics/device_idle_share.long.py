"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals / window), in percent."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
