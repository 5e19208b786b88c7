"""Host time of the engine's step loop per decode step, in milliseconds:
the ``engine.step`` spans less the blocking fetch (``engine.decode.wait``)
and the traced run's statistics (``engine.stats``), over the number of
decode calls (``engine.decode``), from the engine's span totals of the
traced window."""


def read(ctx):
    spans = ctx.summary.get("spans") or {}
    n = spans.get("engine.decode", {}).get("n", 0)
    if not n or "engine.step" not in spans:
        return None
    s = lambda k: spans.get(k, {}).get("s", 0.0)
    return 1000.0 * (s("engine.step") - s("engine.decode.wait")
                     - s("engine.stats")) / n
