"""Model FLOPs of the queries decoded in the traced window (FF tower and
Eq. 3 over the catalog, ``bench/work``) over the window times the chip's
peak, in %."""
from bench import work


def read(ctx):
    steps = ctx.trace.spans_named("bench.step")
    w = ctx.trace.window_s()
    if not steps or w <= 0:
        return None
    c = ctx.config
    per_query = work.retrieval_query_flops(d=c["d"], m=c["m"], k=c["k"],
                                           hidden=c["hidden"])
    live = sum(int(s.stats.get("live", 0)) for s in steps)
    return 100.0 * live * per_query / (w * ctx.peak.flops)
