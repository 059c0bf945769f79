"""Per-token ratios of the LM program's own spans (``_program``).

The tokens of the traced window are the ones the program emitted there:
a step's ``live`` rows each give one (``repro.launch`` ``fn=decode``),
and each prefill (``repro.prefill``) its first.  A program that spans
no decode step in the window (one whose LM path has no spans: its
prefill pool's ``repro.prefill`` alone is no token count) gives None."""
from __future__ import annotations

from bench.layer_metrics import _program


def per_token(ctx, value: float):
    """``value`` over the tokens emitted in the traced window."""
    steps = _program.named(ctx, "repro.launch", fn="decode")
    if not steps:
        return None
    return value / (sum(int(s.stats.get("live", 0)) for s in steps)
                    + len(_program.named(ctx, "repro.prefill")))
