"""Roofline share of the fused Bloom decode-top-k kernel, in %: the least
time the chip could take for the Eq. 3 recovery of the live rows
(``bench/work.decode_topk`` over ``bench/peaks``), over the kernel's
device time, for the kernel calls in the traced window."""
from bench import peaks, work

KERNEL = "bloom_decode_topk_pallas"


def read(ctx):
    calls = ctx.trace.op_events(KERNEL)
    steps = ctx.trace.spans_named("bench.step")
    if not calls or not steps:
        return None
    live = sum(int(s.stats.get("live", 0)) for s in steps) / len(steps)
    c = ctx.config
    ops, nbytes = work.decode_topk(live, d=c["d"], m=c["m"], k=c["k"],
                                   topk=c["topk"])
    t_min, _ = peaks.roofline_s(ctx.peak, ops, nbytes)
    t_kernel = sum(e.dur for e in calls) / 1e9
    return 100.0 * len(calls) * t_min / t_kernel
