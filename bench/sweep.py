"""Find the knee of an open-loop serving cell: the highest offered rate
it serves without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 8,10,12

One process builds and warms the cell's system once, then serves a
window at each rate (the cell's mix with only ``rate_qps`` changed) and
prints, per rate: the p95 of the cell's latencies, the completed
requests per second, and the drain (seconds from the window's close to
the last answer).  Below the knee the drain stays near one service time;
above it the backlog, and so the drain, grows with the window.  The rate
written into a traffic file is a number read off this output; the
benchmark itself never searches for one.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, traffic_gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec(ROOT)
    cell = harness.find(spec["workloads"], args.workload, "workload")
    config = harness.load_json(ROOT / harness.find(
        spec["configs"], cell["config"], "config")["file"])
    traffic = traffic_gen.load(cell["traffic"])
    harness.device_check(cell["chips"])
    harness.configure_jax(ROOT)
    import time
    from bench.drivers import serve_open_loop as drv
    sysmod = harness.load_module(
        harness.BENCH_DIR / "systems" / f"{config['system']}.py",
        f"bench_system_{config['system']}")
    sut, state = drv.build(sysmod, config, traffic, args.seed)
    names = [m["name"] for m in harness.cell_metrics(
        spec, cell["name"], "end_to_end") if m["name"] != "setup_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        t = dict(traffic, rate_qps=rate)
        reqs = traffic_gen.serve_requests(t, config, args.seconds, args.seed)
        spans = harness.Spans(time.perf_counter())
        recs, stats = drv.serve(sut, state, reqs, seconds=args.seconds,
                                drain_s=t["drain_s"], spans=spans)
        end = spans.now()
        done = [r.done for r in recs.values() if r.done >= 0]
        row = {"rate_qps": rate, "requests": len(recs),
               "failed": drv.failed(recs),
               "completed_per_s": len(done) / max(done, default=1.0),
               "drain_s": max(done, default=0.0) - args.seconds,
               **{n: drv.end_to_end(n, recs, end, args.seconds)
                  for n in names},
               **drv.host_summary(recs)}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
