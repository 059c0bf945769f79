"""Open-loop serving on the wall clock.

Requests come due at fixed times (``arrival_step`` holds the due time in
microseconds, see ``traffic_gen``) whether or not the server keeps up, as
from independent users.  The loop calls the program's own
``RequestQueue``, ``Scheduler``, ``PrefillPool`` and ``SlotProgram``
(``prefill``, ``insert``, ``step``, ``emit``) in the order the program's
``engine.run_slot_loop`` does; only its clock is the wall clock instead
of a count of decode steps.

Every latency is taken from the request's due time, so a stall also
delays the requests queued behind it; how late the loop noticed each
request (``late``) is reported beside them.  Requests due in the window
are served to the end or until ``drain_s`` past its close; one not
finished by then is failed and misses every limit.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench.harness import p95


@dataclasses.dataclass
class Rec:
    due: float
    seen: float = -1.0           # when the loop put it in the queue
    admitted: float = -1.0
    tokens: list = dataclasses.field(default_factory=list)  # host times
    done: float = -1.0


def serve(sut, state, requests, *, seconds: float, drain_s: float,
          spans, on_open=None, on_close=None, open_at: float | None = None):
    """Serve ``requests`` (ascending due) and return their records.

    ``on_open`` is called once the clock passes ``open_at`` (the traced
    part of the window starts) and ``on_close`` once it passes
    ``seconds`` (the window closes)."""
    from repro.serving.scheduler import RequestQueue, Scheduler, ServeStats
    # what set-up made (programs, weights, the window's requests) lives
    # as long as the run: freezing it keeps the collector's full passes
    # in the window to what the window itself allocates
    gc.freeze()
    program, pool, params = sut.program, sut.pool, sut.params
    queue = RequestQueue()
    sched = Scheduler(sut.n_slots)
    stats = ServeStats()
    recs = {r.rid: Rec(due=r.arrival_step / 1e6) for r in requests}
    now = spans.now
    nxt, n = 0, len(requests)
    opened = closed = False
    if open_at is None:
        opened = True

    while True:
        t = now()
        if not opened and t >= open_at:
            on_open()
            opened = True
            t = now()
        if not closed and t >= seconds:
            if on_close is not None:
                on_close()
            closed = True
        if t > seconds + drain_s:
            break
        while nxt < n and recs[requests[nxt].rid].due <= t:
            queue.push(requests[nxt])
            recs[requests[nxt].rid].seen = t
            nxt += 1
        t_us = int(t * 1e6)
        with spans.span("bench.admit"):
            admitted = sched.admit(queue, t_us)
            for req in admitted:
                program.check_admit(req)
        if admitted:
            t = now()
            for req in admitted:
                recs[req.rid].admitted = t
            with spans.span("bench.prefill", n=len(admitted),
                            tokens=sum(r.prompt_len for r in admitted),
                            tokens_sq=sum(r.prompt_len ** 2
                                          for r in admitted)):
                prefilled = pool.prefill_all(admitted)
            with spans.span("bench.insert", n=len(admitted)):
                for req, res in zip(admitted, prefilled):
                    if res is None:
                        stats.rejects += 1
                        sched.reject(req.slot, t_us)
                        continue
                    stats.prefills += 1
                    live = program.insert(state, req, res, stats)
                    t = now()
                    recs[req.rid].tokens.extend([t] * len(req.tokens))
                    if not live:
                        sched.release(req.slot, t_us)
                        recs[req.rid].done = t
        if not sched.n_active:
            if nxt >= n and not len(queue):
                break
            if len(queue):
                continue
            wake = recs[requests[nxt].rid].due
            if not opened:
                wake = min(wake, open_at)
            if not closed:
                wake = min(wake, seconds)
            with spans.span("bench.idle"):
                while now() < wake:
                    time.sleep(min(max(wake - now() - 2e-4, 0.0), 1e-3))
            continue
        active = sched.active
        with spans.span("bench.step", **sut.step_meta(active)):
            out = program.step(params, state)
        stats.decode_steps += 1
        t = now()
        with spans.span("bench.emit"):
            for slot, req in list(active.items()):
                had = len(req.tokens)
                retire = program.emit(state, req, slot, out, stats)
                recs[req.rid].tokens.extend([t] * (len(req.tokens) - had))
                if retire:
                    sched.release(slot, t_us)
                    recs[req.rid].done = t
    if not closed and on_close is not None:
        on_close()
    return recs, stats


def failed(recs: dict) -> int:
    return sum(r.done < 0 for r in recs.values())


def _latencies(recs: dict, until: float, what) -> list:
    out = []
    for r in recs.values():
        v = what(r)
        out.append((until if v is None or v < 0 else v) - r.due)
    return out


def end_to_end(name: str, recs: dict, end: float, window: float):
    """The cell's end-to-end metrics from its records: latencies in ms (a
    request that never finished counts as finishing at the run's end),
    ``queries_per_s`` the requests finished inside the window over its
    length."""
    if name == "query_p95_ms":
        return 1e3 * p95(_latencies(recs, end, lambda r: r.done))
    if name == "queries_per_s":
        return sum(0 <= r.done <= window for r in recs.values()) / window
    if name == "ttft_p95_ms":
        return 1e3 * p95(_latencies(
            recs, end, lambda r: r.tokens[0] if r.tokens else None))
    if name == "itl_p95_ms":
        gaps = [b - a for r in recs.values()
                for a, b in zip(r.tokens[:-1], r.tokens[1:])]
        return 1e3 * p95(gaps) if gaps else None
    raise KeyError(f"serve_open_loop has no end-to-end metric {name!r}")


def host_summary(recs: dict) -> dict:
    late = [r.seen - r.due for r in recs.values() if r.seen >= 0]
    wait = [r.admitted - r.due for r in recs.values() if r.admitted >= 0]
    done = [r.done - r.due for r in recs.values() if r.done >= 0]
    q = [round(1e3 * float(v), 3) for v in
         np.percentile(done, [50, 90, 95, 99, 100])] if done else []
    return {"requests": len(recs), "failed": failed(recs),
            "done_ms_p50_p90_p95_p99_max": q,
            "generator_late_p95_ms": 1e3 * (p95(late) or 0.0),
            "generator_late_max_ms": 1e3 * max(late, default=0.0),
            "queue_wait_p95_ms": 1e3 * (p95(wait) or 0.0),
            "tokens": int(sum(len(r.tokens) for r in recs.values()))}


def build(sysmod, config, traffic, seed, **kw):
    """The cell's system from the seed, warmed, with its slot state."""
    from repro.serving.scheduler import ServeStats
    sut = sysmod.System(config, traffic, seed, **kw)
    state = sut.program.init_state(sut.n_slots)
    sut.warm(state, ServeStats())
    return sut, state


def run(ctx):
    """One run of a serving cell: build, warm, serve the window, read the
    device's peak memory, free the program, compare with the reference."""
    from bench import traffic_gen
    sysmod, traffic = ctx.system, ctx.traffic
    requests = traffic_gen.serve_requests(traffic, ctx.config, ctx.seconds,
                                          ctx.seed)
    sut, state = build(sysmod, ctx.config, traffic, ctx.seed)
    ctx.begin_window()
    recs, stats = serve(sut, state, requests, seconds=ctx.seconds,
                        drain_s=traffic["drain_s"], spans=ctx.spans,
                        on_open=ctx.open_trace, on_close=ctx.end_window,
                        open_at=ctx.trace_open_at(traffic))
    end = ctx.spans.now()
    ctx.read_memory()
    del state
    sut.release()
    by_rid = {r.rid: r for r in requests}
    served = [by_rid[rid] for rid, r in recs.items() if r.done >= 0]
    ctx.info.update(host_summary(recs))
    ctx.info["decode_steps"] = stats.decode_steps
    checks = sysmod.check(ctx.config, traffic, ctx.seed, served) \
        if served else []
    return {
        "attempted": len(recs), "failed": failed(recs),
        "end_to_end": lambda name: end_to_end(name, recs, end, ctx.seconds),
        "records": recs, "checks": checks,
    }


def serve_window(sysmod, config, traffic, seconds, seed, **kw) -> list:
    """The requests one window of the cell's mix served to the end."""
    import time
    from bench import harness, traffic_gen
    reqs = traffic_gen.serve_requests(traffic, config, seconds, seed)
    sut, state = build(sysmod, config, traffic, seed, **kw)
    recs, _ = serve(sut, state, reqs, seconds=seconds,
                    drain_s=traffic["drain_s"],
                    spans=harness.Spans(time.perf_counter()))
    del state
    sut.release()
    return [r for r in reqs if recs[r.rid].done >= 0]


def readings(sysmod, config, traffic, seed, seconds) -> list:
    """The program's numbers on one seed: a window at the cell's load."""
    served = serve_window(sysmod, config, traffic, seconds, seed)
    return sysmod.check(config, traffic, seed, served)


def control_readings(sysmod, config, traffic, seed, seconds) -> list:
    """[(what, numbers)] of the system's control on one seed."""
    def window(**kw):
        return serve_window(sysmod, config, traffic, seconds, seed, **kw)
    return [("control", sysmod.control(config, traffic, seed, window))]
