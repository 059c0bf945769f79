"""Host time waiting for the decode step's ids per step, in ms: the
reader of ``step_wait_ms.retrieval`` (``repro.wait`` spans over the
``repro.launch`` spans with ``fn=decode``), which the LM program's spans
answer alike."""
from pathlib import Path

from bench import harness

read = harness.load_module(
    Path(__file__).with_name("step_wait_ms.retrieval.py"),
    "bench_metric_step_wait_ms.retrieval").read
