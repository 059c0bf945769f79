"""Roofline share of the LM's decode steps, in %: the least time the chip
could take for the window's steps (``bench/work_lm.decode_step`` of each
``bench.step`` span's ``live`` rows and attended ``keys``, over
``bench/peaks``), over the device time of the programs those spans
launched."""
from bench import peaks, work_lm


def read(ctx):
    steps = ctx.trace.spans_named("bench.step")
    ns = ctx.trace.device_ns_under(steps) if steps else 0.0
    if ns <= 0:
        return None
    t_min = sum(peaks.roofline_s(ctx.peak, *work_lm.decode_step(
        ctx.config, int(s.stats["live"]), int(s.stats["keys"]),
        ctx.traffic["topk"]))[0] for s in steps)
    return 100.0 * t_min / (ns / 1e9)
