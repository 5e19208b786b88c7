"""Device time of the decode program per decode step in the traced
window, in milliseconds."""


def read(ctx):
    n = ctx.trace.program_n.get("decode", 0)
    if not n:
        return None
    return 1000.0 * ctx.trace.program_s["decode"] / n
