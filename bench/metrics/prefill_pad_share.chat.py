"""Share of the tokens the prefill programs ran that are bucket padding:
1 - the real prompt tokens (``prefill_prompt_tokens``) over the padded
ones (``prefill_tokens``) the engine counted in the traced window, in
percent."""


def read(ctx):
    real = ctx.summary.get("prefill_prompt_tokens")
    padded = ctx.summary.get("prefill_tokens")
    if real is None or not padded:
        return None
    return 100.0 * (1.0 - real / padded)
