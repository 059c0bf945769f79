"""idle_share.retrieval: see ``_shared.idle_share``."""
from bench.layer_metrics._shared import idle_share as read  # noqa: F401
