"""Fixtures of the benchmark's CPU tests: a benchmark root at smoke
sizes (the program's smoke presets), whose drivers, systems and metric
readers are the real ones, and a harness that accepts the CPU."""
from __future__ import annotations

import json
import os
import shutil

import pytest

from bench import harness

BENCH = harness.BENCH_DIR

RETRIEVAL = {"system": "retrieval", "d": 50000, "m": 256, "k": 2,
             "hash_seed": 0, "c_max": 8, "hidden": [32], "topk": 8,
             "limits": {"topk_gap": 1e-3, "score_err": 1e-3}}
LM = {"system": "lm", "program_arch": "qwen1.5-0.5b",
      "program_preset": "smoke", "hidden_size": 64,
      "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
      "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
      "tie_word_embeddings": True, "compute_dtype": "float32",
      "bloom_m": 128, "bloom_k": 3, "bloom_seed": 0,
      "limits": {"token_gap": 1e-3}}
TRAFFIC = {
    "zipf": {"driver": "serve_open_loop", "prompt": "zipf_items",
             "items_per_query": 8, "rate_qps": 40.0, "slots": 4,
             "drain_s": 60, "trace_s": 1, "check_sample": 6},
    "chat": {"driver": "serve_open_loop", "prompt": "uniform_tokens",
             "prompt_lens": [8, 16], "prompt_p": [0.5, 0.5],
             "output_lens": [2, 6], "output_p": [0.5, 0.5],
             "rate_qps": 12.0, "slots": 4, "max_len": 24, "topk": 4,
             "drain_s": 60, "trace_s": 1, "check_tokens": 12},
}
CELLS = [("tiny.zipf", "tiny_retrieval", "zipf"),
         ("tiny.chat", "tiny_lm", "chat")]


def write_root(root, spec=None):
    """A benchmark root at smoke sizes under ``root``."""
    b = root / "bench"
    for d in ("configs", "traffic"):
        (b / d).mkdir(parents=True, exist_ok=True)
    for d in ("drivers", "systems", "layer_metrics"):
        if not (b / d).exists():
            os.symlink(BENCH / d, b / d)
    (b / "configs" / "tiny_retrieval.json").write_text(json.dumps(RETRIEVAL))
    (b / "configs" / "tiny_lm.json").write_text(json.dumps(LM))
    for name, t in TRAFFIC.items():
        (b / "traffic" / f"{name}.json").write_text(json.dumps(t))
    spec = spec or {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in ("tiny_retrieval", "tiny_lm")],
        "workloads": [{"name": c, "config": cf, "traffic": t, "chips": 1}
                      for c, cf, t in CELLS],
        "end_to_end": [_metric(n, u, [c]) for n, u, c in E2E]
        + [_metric("setup_s", "s", None)],
        "per_layer": [_metric(n, u, [c]) for n, u, c in PER_LAYER],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


E2E = [("query_p95_ms", "ms", "tiny.zipf"),
       ("queries_per_s", "queries/s", "tiny.zipf"),
       ("ttft_p95_ms", "ms", "tiny.chat"), ("itl_p95_ms", "ms", "tiny.chat")]
PER_LAYER = [
    ("queue_wait_p95_ms.retrieval", "ms", "tiny.zipf"),
    ("host_ms_per_query.retrieval", "ms", "tiny.zipf"),
    ("decode_step_ms.retrieval", "ms", "tiny.zipf"),
    ("bloom_decode_topk_roofline", "%", "tiny.zipf"),
    ("idle_share.retrieval", "%", "tiny.zipf"),
    ("mfu.retrieval", "%", "tiny.zipf"),
    ("queue_wait_p95_ms.lm", "ms", "tiny.chat"),
    ("decode_step_ms.lm", "ms", "tiny.chat"),
    ("prefill_ms.lm", "ms", "tiny.chat"),
    ("idle_share.lm", "%", "tiny.chat"),
    ("mfu.lm", "%", "tiny.chat"),
]


def _metric(name, unit, cells):
    m = {"name": name, "unit": unit}
    if cells is not None:
        m["workloads"] = cells
    return m


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A smoke-size root, run on the CPU, read against the v5e's peaks."""
    import jax
    from bench import peaks
    monkeypatch.setattr(harness, "device_check",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peaks, "peak_for",
                        lambda kind: peaks.PEAKS["TPU v5e"])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    yield write_root(tmp_path)
    shutil.rmtree(tmp_path / "cache", ignore_errors=True)
