"""Device time of the prefill programs (batched prefill, chunked
prefill, pool insert) per 1000 prompt tokens prefilled in the traced
window, in milliseconds."""


def read(ctx):
    tokens = ctx.work["prefill_tokens"]
    dev = ctx.trace.program_s.get("prefill", 0.0)
    if tokens <= 0 or dev <= 0:
        return None
    return 1000.0 * dev / (tokens / 1000.0)
