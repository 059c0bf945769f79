"""decode_step_ms.lm: see ``_shared.decode_step_ms``."""
from bench.layer_metrics._shared import decode_step_ms as read  # noqa: F401
