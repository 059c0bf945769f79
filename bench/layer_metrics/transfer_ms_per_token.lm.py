"""Host time in transfers per token emitted, in ms: the ``repro.h2d``
(prompt, slot scalars) and ``repro.d2h`` (first token, a step's ids)
spans of the traced window over its tokens (``_per_token``)."""
from bench.layer_metrics import _per_token, _program


def read(ctx):
    return _per_token.per_token(ctx, _program.ms(_program.transfers(ctx)))
