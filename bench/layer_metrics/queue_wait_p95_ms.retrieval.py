"""queue_wait_p95_ms.retrieval: see ``_shared.queue_wait_p95_ms``."""
from bench.layer_metrics._shared import queue_wait_p95_ms as read  # noqa: F401
