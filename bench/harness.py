"""Shared pieces of a benchmark run: the device check, the compile
counter, host spans, percentiles and the lookup of a cell's files by
name.  Nothing here imports the program."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import time

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# lowering happens for every new program, whether or not the persistent
# cache then holds its executable; a backend compile only on a miss
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """Raised where the run finds no accelerator: exit non-zero, print no
    result."""


def device_check(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU (jax.devices()[0].platform is "
                     f"{devs[0].platform!r}); nothing is measured")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def configure_jax(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, given to the program's own ``enable_compile_cache``, with
    every program cached however fast it compiled, so that only the first
    run of a cell in a checkout compiles."""
    import os
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts lowerings and backend compiles through JAX's monitoring
    events, so a run can say how many fell inside its window."""

    def __init__(self):
        self.lowerings = 0
        self.compiles = 0
        self.compile_s = 0.0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        if event == LOWER_EVENT:
            self.lowerings += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self):
        return self.lowerings, self.compiles


class Spans:
    """Host spans of the harness around each call into the program.

    Every span is a ``jax.profiler.TraceAnnotation`` (so a traced run has
    it on the profiler's clock, beside the device ops) and a record kept
    in memory with its metadata (live rows, prompt tokens, ...), on the
    run's own clock.  Outside a trace a TraceAnnotation costs about a
    microsecond."""

    def __init__(self, t0: float):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self.t0 = t0
        self.records: list[tuple[str, float, float, dict]] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def span(self, name: str, **meta):
        return _Span(self, name, meta)


class _Span:
    def __init__(self, owner: Spans, name: str, meta: dict):
        self.owner, self.name, self.meta = owner, name, meta

    def __enter__(self):
        self.ann = self.owner._ann(self.name, **self.meta)
        self.ann.__enter__()
        self.start = self.owner.now()
        return self

    def __exit__(self, *exc):
        end = self.owner.now()
        self.ann.__exit__(*exc)
        self.owner.records.append((self.name, self.start, end, self.meta))
        return False


def longest(records, n: int = 3) -> list:
    """The ``n`` longest host spans, and the longest stretch between two
    spans: [(name, start s, seconds)]."""
    top = sorted(records, key=lambda r: r[1] - r[2])[:n]
    out = [(name, round(a, 4), round(b - a, 4)) for name, a, b, _ in top]
    recs = sorted(records, key=lambda r: r[1])
    gaps = [(b[1] - a[2], a[2]) for a, b in zip(recs, recs[1:])]
    if gaps:
        g, at = max(gaps)
        out.append(("between spans", round(at, 4), round(g, 4)))
    return out


def span_stats(records) -> dict:
    """Per span name: count, total seconds, and the median, 99th
    percentile and longest span in ms."""
    by: dict[str, list] = {}
    for name, a, b, _ in records:
        by.setdefault(name, []).append(b - a)
    out = {}
    for name, d in sorted(by.items()):
        v = np.asarray(d)
        out[name] = [len(d), round(float(v.sum()), 4),
                     round(1e3 * float(np.median(v)), 4),
                     round(1e3 * float(np.percentile(v, 99)), 4),
                     round(1e3 * float(v.max()), 4)]
    return out


class GcTimer:
    """Seconds the interpreter spent collecting garbage, and its longest
    pause, while installed."""

    def __init__(self):
        import gc
        self._gc, self.total, self.longest, self._t = gc, 0.0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.total += d
            self.longest = max(self.longest, d)

    def remove(self):
        self._gc.callbacks.remove(self._on)


def p95(values) -> float | None:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def benchmark_spec(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"bench: {path} is missing")
    return load_json(path)


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"bench: no {what} named {name!r}")


def load_module(path: pathlib.Path, modname: str):
    """Import one file of the benchmark by its path: metric readers and
    drivers are found by name, and a name may hold dots."""
    if not path.is_file():
        raise FileNotFoundError(f"bench: {path} is missing")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``cell`` reports: those whose
    ``workloads`` list names it, or that carry no such list."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


class ReaderContext:
    """What a per-layer metric reader gets: the reduced trace
    (``bench/trace.py``), the run's request records (serving cells), the
    configuration and traffic files, the cell's entry and the chip's
    peaks (``bench/peaks.py``)."""

    def __init__(self, *, trace, records, config, traffic, cell, peak):
        self.trace, self.records = trace, records
        self.config, self.traffic = config, traffic
        self.cell, self.peak = cell, peak
