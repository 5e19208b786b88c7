"""Share of the page table the decode scout walks: 100 x the mean table
columns the Pallas paged scout kernel walked (the int8 K pages it DMA'd,
``scout_pages``) per layer and active slot-step (``scout_samples``),
over a slot's table columns (``max_len // page_size``), in percent. A
program without the counter, or one whose scout never ran the kernel,
gives nothing."""


def read(ctx):
    samples = ctx.summary.get("scout_samples")
    if not samples:
        return None
    dep = ctx.config["deployment"]
    columns = dep["max_len"] // dep["page_size"]
    return 100.0 * ctx.summary["scout_pages"] / samples / columns
