"""Model FLOPs of all prefill and decode work the engine did in the
traced window, counted from shapes (attention over the full causal or
windowed context, pruning not subtracted), over the window times the
chips' bf16 peak, in percent."""


def read(ctx):
    flops = ctx.work["decode_flops"] + ctx.work["prefill_flops"]
    if flops <= 0:
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.trace.window_s * peak)
