"""Time to first token at the 95th percentile (nearest rank) over the
requests due in the traced window, in seconds: from when each request was
due to its first token, a failed request +inf. The traced window holds
few requests (about 29 at 3.6 req/s over 8 s), so this tail is a rough
one; the TTFT tail is not an end-to-end metric because over a full
window it swings between two prompt-length classes (PERF.md)."""

import math


def read(ctx):
    ttfts = sorted(ctx.work.get("ttfts") or [])
    if not ttfts:
        return None
    return ttfts[max(0, math.ceil(0.95 * len(ttfts)) - 1)]
