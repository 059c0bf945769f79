"""Host time waiting for the decode step's results per step, in ms: the
``repro.wait`` spans of the traced window over its ``repro.launch`` spans
with ``fn=decode``."""
from bench.layer_metrics import _program


def read(ctx):
    steps = _program.named(ctx, "repro.launch", fn="decode")
    return _program.ms(_program.named(ctx, "repro.wait")) / len(steps) \
        if steps else None
