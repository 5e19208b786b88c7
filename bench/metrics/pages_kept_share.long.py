"""Share of allocated KV pages the HDP scout keeps (fetches) per decode
step: 1 - the engine's page sparsity over the traced window, in
percent."""


def read(ctx):
    if not ctx.summary.get("page_samples"):
        return None
    return 100.0 * (1.0 - ctx.summary["page_sparsity"])
