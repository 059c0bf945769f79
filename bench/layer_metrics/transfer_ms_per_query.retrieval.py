"""Host time in transfers per query, in ms: the ``repro.h2d`` and
``repro.d2h`` spans of the traced window over the queries prefilled in
it."""
from bench.layer_metrics import _program


def read(ctx):
    return _program.per_query(ctx, _program.ms(_program.transfers(ctx)))
