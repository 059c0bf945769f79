"""Readers shared by metrics that differ only in the cells they report
in (the suffix of a metric's name says which end-to-end metric it
moves)."""
from bench.harness import p95


def queue_wait_p95_ms(ctx):
    """p95 over the window's requests of the wait from due time to
    admission into a slot, in ms (harness host clock)."""
    if not ctx.records:
        return None
    v = p95(r.admitted - r.due for r in ctx.records.values()
            if r.admitted >= 0)
    return None if v is None else 1e3 * v


def decode_step_ms(ctx):
    """Device time per decode step call (the programs launched under the
    harness's ``bench.step`` spans in the traced window), in ms."""
    spans = ctx.trace.spans_named("bench.step")
    if not spans:
        return None
    ns = ctx.trace.device_ns_under(spans)
    return ns / len(spans) / 1e6 if ns > 0 else None


def idle_share(ctx):
    """Share of the traced window in which no program ran on the device,
    in %."""
    v = ctx.trace.idle_share()
    return None if v is None else 100.0 * v
