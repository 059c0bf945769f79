"""Device time per prefilled request (the programs launched under the
harness's ``bench.prefill`` spans in the traced window, over the
requests those spans prefilled), in ms."""


def read(ctx):
    spans = ctx.trace.spans_named("bench.prefill")
    n = sum(int(s.stats.get("n", 0)) for s in spans)
    if not n:
        return None
    ns = ctx.trace.device_ns_under(spans)
    return ns / n / 1e6 if ns > 0 else None
