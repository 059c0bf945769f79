"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Layout of a TPU trace as ``jax.profiler.ProfileData`` reads it:

* one plane per chip, ``/device:TPU:<n>``, with the line ``XLA Modules``
  (one event per execution of a compiled program, named
  ``jit_<fn>(<fingerprint>)``) and the line ``XLA Ops`` (one event per
  HLO instruction run, named by the instruction's text; a ``while`` or
  ``call`` event encloses the events of its body on the same line);
* the plane ``/host:CPU``, whose ``python`` line holds the harness's own
  spans (``jax.profiler.TraceAnnotation`` named ``bench.*``, with their
  keyword arguments as stats).

Device and host events share one time axis, to within about a
millisecond.  So a device program is attributed to the harness span that
it overlaps most, and never by exact containment.

Names are made stable: a program's fingerprint and an instruction's
numeric suffix are dropped, so ``%bloom_decode_topk_pallas.1 = ...``
reads ``bloom_decode_topk_pallas``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

_OP_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")
_MODULE_NAME = re.compile(r"^(.*?)(?:\(\d+\))?$")


def op_name(text: str) -> str:
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def module_name(text: str) -> str:
    return _MODULE_NAME.match(text).group(1)


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    end: float            # ns
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Chip:
    modules: list         # [Event], by start
    ops: list             # [Event] with stats["self"] = self time (ns)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(events):
    """Self time of each event of one line: its duration less the events
    it encloses (a while loop less its body)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    stack = []
    for e in evs:
        e.stats["self"] = e.dur
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            stack[-1].stats["self"] -= e.dur
        stack.append(e)
    return evs


@dataclasses.dataclass
class Trace:
    chips: list           # [Chip], one per device plane with events
    spans: list           # host harness spans [Event], by start
    window: tuple         # (start ns, end ns)

    # -- whole device -------------------------------------------------
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, chip):
        return _union(_clip([(e.start, e.end) for e in chip.modules],
                            *self.window))

    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        tot = sum(e - s for c in self.chips for s, e in self._busy(c))
        return tot / len(self.chips) / 1e9

    def idle_share(self) -> float | None:
        w = self.window_s()
        return None if w <= 0 or not self.chips else 1.0 - self.busy_s() / w

    # -- harness spans ------------------------------------------------
    def spans_named(self, name: str, inside: bool = True):
        lo, hi = self.window
        return [s for s in self.spans if s.name == name
                and (not inside or (s.start >= lo and s.end <= hi))]

    def _owner(self, start, end):
        """The harness span that overlaps [start, end) most; failing that,
        the nearest one."""
        best, best_ov = None, 0.0
        i = bisect.bisect_right(self._starts, end)
        for s in self.spans[max(0, i - 64):i]:
            ov = min(end, s.end) - max(start, s.start)
            if ov > best_ov:
                best, best_ov = s, ov
        if best is None:
            near = self.spans[max(0, i - 64):i + 1]
            if near:
                best = min(near, key=lambda s: max(s.start - end,
                                                   start - s.end))
        return best

    def __post_init__(self):
        self.spans.sort(key=lambda s: s.start)
        self._starts = [s.start for s in self.spans]

    def device_ns_under(self, spans) -> float:
        """Device time of the programs attributed to ``spans``, averaged
        over the chips."""
        ids = {id(s) for s in spans}
        tot = 0.0
        for c in self.chips:
            for m in c.modules:
                owner = self._owner(m.start, m.end)
                if owner is not None and id(owner) in ids:
                    tot += m.dur
        return tot / max(len(self.chips), 1)

    def window_fraction(self, module: str) -> float:
        """How many executions of ``module`` the window holds, each
        counted by the share of its run that lies inside the window."""
        lo, hi = self.window
        n = 0.0
        for c in self.chips:
            for m in c.modules:
                if m.name == module and m.dur > 0:
                    n += max(0.0, min(hi, m.end) - max(lo, m.start)) / m.dur
        return n / max(len(self.chips), 1)

    def busiest_module(self) -> str | None:
        acc = defaultdict(float)
        for c in self.chips:
            for m in c.modules:
                acc[m.name] += m.dur
        return max(acc, key=acc.get) if acc else None

    # -- ops ----------------------------------------------------------
    def op_events(self, name: str):
        lo, hi = self.window
        return [e for c in self.chips for e in c.ops
                if op_name(e.name) == name and e.start >= lo and e.end <= hi]

    def top_ops(self, n: int = 10):
        """[(op, seconds of self time)] of the ops that took most device
        time in the window, summed over the chips; an op that straddles
        the window's edge counts by the share of it inside."""
        acc = defaultdict(float)
        lo, hi = self.window
        for c in self.chips:
            for e in c.ops:
                inside = min(hi, e.end) - max(lo, e.start)
                if inside > 0:
                    acc[op_name(e.name)] += e.stats["self"] * inside / e.dur
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10):
        """[(host span, seconds)]: the device's idle time in the window,
        each gap given to the harness span that overlaps it most, summed
        by span name (``none`` where no span overlaps)."""
        acc = defaultdict(float)
        lo, hi = self.window
        for c in self.chips:
            busy = self._busy(c)
            edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    owner = self._owner(s, e)
                    ov = (0.0 if owner is None else
                          min(e, owner.end) - max(s, owner.start))
                    acc[owner.name if ov > 0 else "none"] += e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / max(len(self.chips), 1)] for k, v in top]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def reduce(pd, window: tuple | None = None) -> Trace:
    """``pd``: a ``jax.profiler.ProfileData``.  The window is the
    ``bench.window`` span where the trace has one, else ``window``, else
    the extent of the device programs."""
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [Event(module_name(e.name), e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    ops = _self_times([Event(e.name, e.start_ns,
                                             e.start_ns + e.duration_ns)
                                       for e in line.events])
            if mods:
                chips.append(Chip(sorted(mods, key=lambda e: e.start), ops))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns,
                                           _stats(e)))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    elif window is None:
        ends = [(m.start, m.end) for c in chips for m in c.modules]
        window = ((min(s for s, _ in ends), max(e for _, e in ends))
                  if ends else (0.0, 0.0))
    return Trace(chips, [s for s in spans if s.name != WINDOW_SPAN], window)


def newest_xplane(profile_dir: str) -> str:
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(files, key=os.path.getmtime)


def load(profile_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(newest_xplane(profile_dir)))
