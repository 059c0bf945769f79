"""Records ``data/v5e_retrieval_spans.xplane.pb``, the trace that
``test_program_spans.py`` reads, on one TPU v5e:

    python3 bench/tests/record_spans.py [out.xplane.pb]

The ``movielens`` deployment at 8 slots, warmed, serves 16 of the
``zipf_overload`` mix's queries, all due at once, through the benchmark's
own serving loop.  So the loop runs two iterations, each prefilling and
inserting 8 queries and decoding one step, and the trace (inside its
``bench.window`` span) holds all of them.  The Python tracer is off, which
keeps the file small: no reader needs Python's function calls.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, traffic_gen, trace  # noqa: E402
from bench.drivers import serve_open_loop  # noqa: E402

SLOTS, ITERATIONS, SEED = 8, 2, 2147483711
OUT = pathlib.Path(__file__).parent / "data" / "v5e_retrieval_spans.xplane.pb"


def main(out: pathlib.Path = OUT) -> int:
    import jax
    harness.device_check(1)
    harness.configure_jax(ROOT)
    config = harness.load_json(ROOT / "bench" / "configs" / "movielens.json")
    traffic = dict(traffic_gen.load("zipf_overload"), slots=SLOTS)
    n = SLOTS * ITERATIONS
    requests = traffic_gen.serve_requests(
        traffic, config, n / traffic["rate_qps"], SEED)
    assert len(requests) == n
    for r in requests:
        r.arrival_step = 0
    sysmod = harness.load_module(ROOT / "bench" / "systems" / "retrieval.py",
                                 "bench_system_retrieval")
    sut, state = serve_open_loop.build(sysmod, config, traffic, SEED)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="record_spans_")
    window = []

    def on_open():
        # a span made before the trace starts is never recorded
        jax.profiler.start_trace(tmp, profiler_options=options)
        window.append(jax.profiler.TraceAnnotation("bench.window"))
        window[0].__enter__()

    def on_close():
        window[0].__exit__(None, None, None)
        jax.profiler.stop_trace()

    serve_open_loop.serve(sut, state, requests, seconds=60.0, drain_s=0.0,
                          spans=harness.Spans(time.perf_counter()),
                          on_open=on_open, on_close=on_close, open_at=0.0)
    shutil.copyfile(trace.newest_xplane(tmp), out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {out.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(*(pathlib.Path(a) for a in sys.argv[1:])))
