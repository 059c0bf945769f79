"""Launches of device programs per token emitted: the ``repro.launch``
spans of the traced window (a request's prompt reshape, prefill,
first-token recovery, insert, slot update and retirement; a step's
decode, two eager slices and state advance) over its tokens
(``_per_token``)."""
from bench.layer_metrics import _per_token, _program


def read(ctx):
    return _per_token.per_token(ctx, len(_program.named(ctx,
                                                        "repro.launch")))
