"""Device time of ``hdp_paged_fum_decode`` against its roofline: the
larger of the bytes it DMAs over the HBM peak and its FLOPs over the bf16
peak (``bench.counts.paged_kernel_cost``, from the engine's page
sparsity), over the kernel's measured time, in percent."""

from bench.counts import paged_kernel_cost, roofline_s

KERNEL = "hdp_paged_fum_decode"


def read(ctx):
    t = ctx.trace.kernel_s.get(KERNEL, 0.0)
    sparsity = ctx.summary.get("page_sparsity")
    if t <= 0 or not ctx.summary.get("page_samples"):
        return None
    layers = ctx.dims["L"]
    kept = (1.0 - sparsity) * ctx.work["slot_step_pages"] * layers
    calls = ctx.trace.kernel_n.get(KERNEL, 0)
    dep = ctx.config["deployment"]
    b, f = paged_kernel_cost(ctx.config, kept_pages=kept, calls=calls,
                             slots=dep["max_batch"],
                             table_pages=dep["max_len"] // dep["page_size"])
    return 100.0 * roofline_s(b, f, ctx.peaks) / t
