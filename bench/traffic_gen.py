"""The one generator every traffic mix goes through.

A mix is a data file under ``bench/traffic/`` (see ``load``).  For an
open-loop serving mix the generator turns it into requests with a due
time in seconds:

* ``rate_qps`` fixes the number of requests in a window,
  ``n = round(rate_qps * seconds)``.  Their gaps are the ``n`` midpoint
  quantiles of the exponential law at that rate, in an order drawn from
  the seed, scaled so that all ``n`` fall inside the window.  So every
  seed offers the same work at the same mean rate and the same spread of
  gaps, in a different order: the seed changes which request comes when,
  not how much load there is.
* Categorical lengths (``prompt_lens``/``prompt_p``,
  ``output_lens``/``output_p``) are dealt the same way: each length
  appears ``n * p`` times (largest remainder), in an order drawn from the
  seed.
* Prompt contents are drawn from the seed: uniform token ids over the
  vocabulary (``"prompt": "uniform_tokens"``), or ``items_per_query``
  distinct catalog items from the bounded Zipf(1) law
  (``"prompt": "zipf_items"``, copied from the program's
  ``serving/loadgen.retrieval_workload`` draw).
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from repro.serving.scheduler import Request

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def load(name: str, traffic_dir: pathlib.Path | None = None) -> dict:
    path = (traffic_dir or TRAFFIC_DIR) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r}: {path} is missing")
    return json.loads(path.read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams of one seed (any size of whole number)."""
    return np.random.default_rng([stream, int(seed)])


def arrival_times(rate_qps: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in seconds, ascending, all in [0, seconds)."""
    if not rate_qps > 0:
        raise ValueError(f"rate_qps must be > 0, got {rate_qps}")
    n = max(1, int(round(rate_qps * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = rng_for(seed, 0).permutation(-np.log1p(-q) / rate_qps)
    starts = np.cumsum(gaps) - gaps           # first request due at 0
    return starts * (seconds / gaps.sum())


def deal(values, probs, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of a categorical law dealt exactly: value i appears
    round(n * p_i) times (largest remainder), in random order."""
    p = np.asarray(probs, np.float64)
    if len(p) != len(values) or np.any(p < 0) or not p.sum() > 0:
        raise ValueError(f"bad categorical law {values} / {probs}")
    want = n * p / p.sum()
    count = np.floor(want).astype(int)
    for i in np.argsort(-(want - count), kind="stable")[:n - count.sum()]:
        count[i] += 1
    return rng.permutation(np.repeat(np.asarray(values), count))


def zipf_items(rng: np.random.Generator, catalog: int, size: int
               ) -> np.ndarray:
    """Bounded Zipf(1) draws over [0, catalog), head at id 0: the inverse
    CDF of the log-uniform density, so item i draws with probability
    proportional to ln((i+2)/(i+1))."""
    u = rng.random(size)
    return np.floor(np.exp(u * np.log(float(catalog) + 1.0))
                    ).astype(np.int64) - 1


def distinct_zipf_items(rng, catalog: int, want: int) -> np.ndarray:
    items = list(dict.fromkeys(zipf_items(rng, catalog, 4 * want + 16)
                               .tolist()))[:want]
    while len(items) < want:
        extra = rng.integers(0, catalog, size=want)
        items.extend(v for v in dict.fromkeys(extra.tolist())
                     if v not in set(items))
        items = items[:want]
    return np.asarray(items, np.int32)


def serve_requests(traffic: dict, config: dict, seconds: float, seed: int
                   ) -> list[Request]:
    """The open-loop mix of one window: requests with ``arrival_step`` the
    due time in microseconds, ascending, rids in due order."""
    if "rate_qps" not in traffic:
        raise ValueError("the mix states no rate_qps: read one off a knee "
                         "sweep on the chip (bench/sweep.py)")
    due = arrival_times(traffic["rate_qps"], seconds, seed)
    n = len(due)
    rng = rng_for(seed, 1)
    kind = traffic["prompt"]
    if kind == "zipf_items":
        c = traffic["items_per_query"]
        prompts = [distinct_zipf_items(rng, config["d"], c)
                   for _ in range(n)]
        outs = np.ones(n, int)
        rkind = "oneshot"
    elif kind == "uniform_tokens":
        lens = deal(traffic["prompt_lens"], traffic["prompt_p"], n, rng)
        outs = deal(traffic["output_lens"], traffic["output_p"], n, rng)
        prompts = [rng.integers(0, config["vocab_size"], size=int(L),
                                dtype=np.int32) for L in lens]
        rkind = "lm"
    else:
        raise ValueError(f"unknown prompt kind {kind!r}")
    return [Request(rid=i, prompt=prompts[i], max_gen=int(outs[i]),
                    arrival_step=int(round(due[i] * 1e6)), kind=rkind)
            for i in range(n)]


def prompt_lengths(traffic: dict) -> list[int]:
    """Every prompt length the mix can send: the shapes set-up warms."""
    if traffic["prompt"] == "zipf_items":
        return [traffic["items_per_query"]]
    return sorted(set(int(v) for v in traffic["prompt_lens"]))
