"""The harness end to end at smoke sizes on the CPU, through
``run.main`` with the look for a chip stubbed out (the command line
itself refuses a machine without a TPU)."""
from __future__ import annotations

import json
import time

import numpy as np
import pytest

from bench import harness, run, traffic_gen
from bench.drivers import serve_open_loop
from bench.tests.conftest import LM, RETRIEVAL, TRAFFIC


def _main(root, capsys, workload, seed=2**31 + 5, trace=0, seconds=2):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return out, json.loads(out[-1])


@pytest.mark.parametrize("workload,metrics", [
    ("tiny.zipf", {"query_p95_ms", "queries_per_s", "setup_s"}),
    ("tiny.chat", {"ttft_p95_ms", "itl_p95_ms", "setup_s"}),
])
def test_cells_run_and_are_correct(tiny_root, capsys, workload, metrics):
    out, res = _main(tiny_root, capsys, workload)
    assert res["correct"] is True, res
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert list(res)[-1] == "checks"
    assert any(line.startswith("compiles in window: 0 lowerings")
               for line in out), out


def test_no_chip_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", "movielens.zipf_overload", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_added_files_are_found_by_name(tiny_root, capsys):
    """A configuration, a traffic mix and a metric reader, each added
    as a file under its directory, run without an edit elsewhere."""
    b = tiny_root / "bench"
    (b / "configs" / "tiny_other.json").write_text(json.dumps(
        dict(RETRIEVAL, c_max=8)))
    (b / "traffic" / "burst.json").write_text(json.dumps(
        dict(TRAFFIC["zipf"], rate_qps=25.0)))
    metrics = tiny_root / "metrics"
    metrics.mkdir()
    for f in (b / "layer_metrics").iterdir():
        (metrics / f.name).symlink_to(f)
    (metrics / "answers.tiny.py").write_text(
        "def read(ctx):\n    return float(len(ctx.records))\n")
    (b / "layer_metrics").unlink()
    (b / "layer_metrics").symlink_to(metrics)
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_other",
                            "file": "bench/configs/tiny_other.json"})
    spec["workloads"].append({"name": "other.burst", "config": "tiny_other",
                              "traffic": "burst", "chips": 1})
    spec["per_layer"].append({"name": "answers.tiny", "unit": "1",
                              "workloads": ["other.burst"]})
    spec["end_to_end"][0]["workloads"].append("other.burst")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    _, res = _main(tiny_root, capsys, "other.burst", trace=1)
    assert res["correct"] is True
    assert res["metrics"]["answers.tiny"]["value"] == 50.0
    assert "busy_s" in res["device"] and "breakdown" in res


def test_arrivals_are_pure_in_the_seed():
    mix = dict(TRAFFIC["chat"], rate_qps=20.0)
    a = traffic_gen.serve_requests(mix, LM, 5.0, 2**40 + 3)
    b = traffic_gen.serve_requests(mix, LM, 5.0, 2**40 + 3)
    c = traffic_gen.serve_requests(mix, LM, 5.0, 7)
    key = lambda rs: [(r.arrival_step, r.max_gen, r.prompt.tolist())  # noqa
                      for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # another seed offers the same work in another order
    assert len(a) == len(c) == 100
    assert sorted(r.max_gen for r in a) == sorted(r.max_gen for r in c)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in c)
    gaps = lambda rs: np.diff([r.arrival_step for r in rs])  # noqa
    assert np.median(gaps(a)) == pytest.approx(np.median(gaps(c)), rel=0.02)
    assert all(0 <= r.arrival_step < 5e6 for r in a)


def test_a_mix_without_a_rate_is_refused():
    mix = {k: v for k, v in TRAFFIC["chat"].items() if k != "rate_qps"}
    with pytest.raises(ValueError, match="rate_qps"):
        traffic_gen.serve_requests(mix, LM, 5.0, 1)


def test_zipf_items_are_distinct_and_in_the_catalog():
    reqs = traffic_gen.serve_requests(TRAFFIC["zipf"], RETRIEVAL, 2.0, 11)
    for r in reqs:
        assert len(set(r.prompt.tolist())) == 8
        assert (r.prompt >= 0).all() and (r.prompt < RETRIEVAL["d"]).all()


class _SlowOneSlot:
    """A one-slot one-shot system whose decode step takes 50 ms."""

    n_slots = 1

    def __init__(self):
        from repro.serving.engine import SlotProgram

        class Program(SlotProgram):
            kind = "oneshot"

            def check_admit(self, req):
                pass

            def prefill(self, params, req, device=None):
                return (req.rid, None)

            def insert(self, state, req, payload, stats):
                return True

            def step(self, params, state):
                time.sleep(0.05)
                return None

            def emit(self, state, req, slot, out, stats):
                req.tokens.append(req.rid)
                return True

        from repro.serving.engine import PrefillPool
        self.program = Program()
        self.pool = PrefillPool(None, None, topk=1, program=self.program)
        self.params = None

    @staticmethod
    def step_meta(active):
        return {"live": len(active)}


def test_latency_is_taken_from_the_due_time():
    from repro.serving.scheduler import Request
    reqs = [Request(rid=i, prompt=np.zeros(1, np.int32), max_gen=1,
                    arrival_step=due, kind="oneshot")
            for i, due in enumerate((0, 1000))]
    spans = harness.Spans(time.perf_counter())
    recs, _ = serve_open_loop.serve(_SlowOneSlot(), None, reqs, seconds=0.01,
                                    drain_s=5, spans=spans)
    first, second = recs[0], recs[1]
    assert second.due == pytest.approx(0.001)
    # the second request waited for the first one's step: its latency
    # runs from its due time, through that wait, to its own answer
    assert second.admitted >= first.done
    assert second.done - second.due >= 0.099
    assert serve_open_loop.end_to_end("query_p95_ms", recs, spans.now(),
                                      0.01) >= 0.09e3
