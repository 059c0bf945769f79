"""Model FLOPs of the prefills and decode steps in the traced window
(``bench/work``: matmuls, attention over the keys each position attends,
Eq. 3 recovery) over the window times the chip's peak, in %."""
from bench import work


def read(ctx):
    t, c = ctx.trace, ctx.config
    w = t.window_s()
    pre, dec = t.spans_named("bench.prefill"), t.spans_named("bench.step")
    if w <= 0 or not (pre or dec):
        return None
    flops = sum(work.lm_prefill_flops_sum(
        c, int(s.stats["n"]), int(s.stats["tokens"]),
        int(s.stats["tokens_sq"])) for s in pre)
    flops += sum(work.lm_decode_flops(c, int(s.stats["live"]),
                                      int(s.stats["keys"])) for s in dec)
    return 100.0 * flops / (w * ctx.peak.flops)
