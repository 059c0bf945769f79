"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
and a traffic mix (``bench/traffic/<name>.json``); the mix names its
driver (``bench/drivers/<driver>.py``) and the configuration its system
(``bench/systems/<system>.py``).  Per-layer metrics are read by
``bench/layer_metrics/<metric>.py``.  Adding a cell, a mix or a metric
adds files and entries; nothing here changes.

A run: checks for the chip (exits non-zero without one, printing no
result), makes the weights on the device from the seed, warms every
shape the window uses, measures for ``--seconds``, counts the compiles
inside the window, reads the device's peak memory, frees the program,
compares what the window produced with the plain reference, and prints
one JSON line last.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles the last part of the window and reports
its per-layer metrics, the device's busy time and a breakdown.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, traffic_gen  # noqa: E402


class Run:
    """What a driver needs from the harness during one run."""

    def __init__(self, bench_dir, cell, config, traffic, seed, seconds,
                 trace):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.system = harness.load_module(
            bench_dir / "systems" / f"{config['system']}.py",
            f"bench_system_{config['system']}")
        self.counter = harness.CompileCounter().install()
        self.info: dict = {}
        self.spans = None
        self.setup_s = None
        self.window_compiles = None
        self.memory_peak_bytes = None
        self._profile_dir = None
        self._window_span = None
        self._at_open = None

    # -- window -------------------------------------------------------
    def begin_window(self):
        self.spans = harness.Spans(time.perf_counter())
        self.setup_s = self.spans.t0 - T_START
        self._at_open = self.counter.snapshot()
        self._gc = harness.GcTimer()

    def end_window(self):
        """The window closes: count its compiles and end the trace (the
        drain that follows is served untraced)."""
        if self.window_compiles is None:
            now = self.counter.snapshot()
            self.window_compiles = tuple(b - a for a, b in
                                         zip(self._at_open, now))
            self._gc.remove()
            self.info.update(
                gc_s=round(self._gc.total, 4),
                gc_longest_s=round(self._gc.longest, 4),
                longest_spans=harness.longest(self.spans.records),
                spans_n_s_p50_p99_max=harness.span_stats(self.spans.records))
        if self._window_span is not None:
            import jax
            self._window_span.__exit__(None, None, None)
            self._window_span = None
            jax.profiler.stop_trace()

    def trace_open_at(self, traffic):
        if not self.trace:
            return None
        return max(0.0, self.seconds - traffic["trace_s"])

    def open_trace(self):
        import jax
        self._profile_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._profile_dir)
        self._window_span = jax.profiler.TraceAnnotation("bench.window")
        self._window_span.__enter__()

    def read_memory(self):
        import jax
        devs = jax.devices()[:self.cell["chips"]]
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devs)


def _number(v):
    return v if v is None or math.isfinite(v) else None


def main(argv=None, root: Path = ROOT) -> int:
    """``root`` holds ``BENCHMARK.json`` and the benchmark's directory
    ``bench/``; every file of a cell is looked up under it by name."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_dir = root / "bench"
    spec = harness.benchmark_spec(root)
    cell = harness.find(spec["workloads"], args.workload, "workload")
    centry = harness.find(spec["configs"], cell["config"], "config")
    config = harness.load_json(root / centry["file"])
    traffic = traffic_gen.load(cell["traffic"], bench_dir / "traffic")

    try:
        devices = harness.device_check(cell["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    harness.configure_jax(root)

    run = Run(bench_dir, cell, config, traffic, args.seed, args.seconds,
              bool(args.trace))
    driver = harness.load_module(
        bench_dir / "drivers" / f"{traffic['driver']}.py",
        f"bench_driver_{traffic['driver']}")
    out = driver.run(run)

    lower, comp = run.window_compiles
    print(f"compiles in window: {lower} lowerings, {comp} backend "
          f"compiles", flush=True)
    print("host: " + json.dumps(run.info), flush=True)

    metrics, device_extra, breakdown = {}, {}, None
    if not args.trace:
        for m in harness.cell_metrics(spec, cell["name"], "end_to_end"):
            v = run.setup_s if m["name"] == "setup_s" \
                else out["end_to_end"](m["name"])
            if _number(v) is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from bench import peaks, trace as trace_lib
        tr = trace_lib.load(run._profile_dir)
        ctx = harness.ReaderContext(
            trace=tr, records=out["records"], config=config,
            traffic=traffic, cell=cell,
            peak=peaks.peak_for(devices[0].device_kind))
        for m in harness.cell_metrics(spec, cell["name"], "per_layer"):
            reader = harness.load_module(
                bench_dir / "layer_metrics" / f"{m['name']}.py",
                f"bench_metric_{m['name']}")
            v = _number(reader.read(ctx))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        import shutil
        shutil.rmtree(run._profile_dir, ignore_errors=True)
        device_extra = {"busy_s": tr.busy_s(), "window_s": tr.window_s()}
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}

    limits = config.get("limits", {})
    checks = {name: {"value": _number(v), "limit": limits.get(name)}
              for name, v in out["checks"]}
    correct = (bool(checks) and out["failed"] == 0 and all(
        c["value"] is not None and c["limit"] is not None
        and c["value"] <= c["limit"] for c in checks.values()))
    dev = devices[0]
    result = {
        "correct": correct, "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": run.memory_peak_bytes,
                   **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
