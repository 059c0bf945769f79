"""The program's own spans (``repro.*``, written by the program's
``repro.tracing``) in a traced run, and the counts and host times per
query and per step that the serving-loop readers report.

``bench/trace.py`` keeps only the harness's ``bench.*`` spans.  So the
readers here read the run's ``.xplane.pb`` once more for the host events
named ``repro.*``: the newest trace under the temporary directory whose
``bench.window`` span is the reduced trace's window.  The program's spans
lie on the profiler's clock beside the device's programs; nesting on the
host thread gives a span's parent (a launch inside a ``repro.prefill``).

A query is one ``repro.prefill`` span inside the window, a step one
``repro.launch`` span with ``fn=decode``.  Where the program emits no
such spans, every reader returns None.

Each device program runs after the start of the span that dispatched
it, once the device's clock is read as a constant offset behind the
host's: 0.4-1.0 ms in traced runs of ``movielens.zipf_overload`` and
1.3-1.8 ms on the test recording (TPU v5e).  So the clocks agree to
within about 2 ms, not the millisecond ``bench/trace.py`` states.
"""
from __future__ import annotations

import glob
import os
import tempfile

from bench import trace

PROGRAM_PREFIX = "repro."


def program_spans(pd) -> tuple[list, tuple | None]:
    """The host events named ``repro.*`` of ``pd`` (a
    ``jax.profiler.ProfileData``), by start, with their args as stats,
    and the ``bench.window`` span's (start, end) where it has one."""
    spans, window = [], None
    for plane in pd.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    spans.append(trace.Event(e.name, e.start_ns,
                                             e.start_ns + e.duration_ns,
                                             dict(e.stats)))
                elif e.name == trace.WINDOW_SPAN and window is None:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
    return sorted(spans, key=lambda s: s.start), window


def program_spans_named(spans, window, name: str, inside: bool = True,
                        **stats):
    """The spans called ``name`` whose stats hold ``stats``; with
    ``inside``, only those that lie wholly in ``window`` (the rule of
    ``Trace.spans_named``)."""
    lo, hi = window
    return [s for s in spans if s.name == name
            and all(s.stats.get(k) == v for k, v in stats.items())
            and (not inside or (s.start >= lo and s.end <= hi))]


def _run_spans(window) -> list:
    from jax.profiler import ProfileData
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        spans, win = program_spans(ProfileData.from_file(path))
        if win == window:
            return spans
    return []


def named(ctx, name: str, **stats):
    """The program's spans called ``name`` inside the traced window."""
    if not hasattr(ctx, "program_spans"):
        ctx.program_spans = _run_spans(ctx.trace.window)
    return program_spans_named(ctx.program_spans, ctx.trace.window, name,
                               **stats)


def ms(spans) -> float:
    return sum(s.dur for s in spans) / 1e6


def per_query(ctx, value: float):
    """``value`` over the queries prefilled in the traced window."""
    queries = len(named(ctx, "repro.prefill"))
    return value / queries if queries else None


def transfers(ctx):
    return named(ctx, "repro.h2d") + named(ctx, "repro.d2h")
