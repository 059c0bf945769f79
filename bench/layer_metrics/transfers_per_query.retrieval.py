"""Host-device transfers per query: ``repro.h2d`` (items, slot index,
live mask) and ``repro.d2h`` (ids, scores) spans over the queries
prefilled in the traced window."""
from bench.layer_metrics import _program


def read(ctx):
    return _program.per_query(ctx, len(_program.transfers(ctx)))
