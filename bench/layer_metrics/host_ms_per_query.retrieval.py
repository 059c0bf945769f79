"""Host time of the serving loop per query, in ms: the harness spans
around the program's host code (admit, prefill, insert, emit) in the
traced window, over the queries those prefill spans took in.  The decode
step's span is left out: its wait is the device's."""
SPANS = ("bench.admit", "bench.prefill", "bench.insert", "bench.emit")


def read(ctx):
    n = sum(int(s.stats.get("n", 0))
            for s in ctx.trace.spans_named("bench.prefill"))
    if not n:
        return None
    ns = sum(s.dur for name in SPANS for s in ctx.trace.spans_named(name))
    return ns / n / 1e6
