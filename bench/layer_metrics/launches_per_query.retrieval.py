"""Launches of device programs per query: ``repro.launch`` spans (the
prefill, the row index, the insert, the decode step) over the queries
prefilled in the traced window."""
from bench.layer_metrics import _program


def read(ctx):
    return _program.per_query(ctx, len(_program.named(ctx, "repro.launch")))
