"""Profiler trace capture and its reduction to metrics.

The reduction works on plain event tuples ``(plane, line, name, start_ns,
dur_ns)`` so that it can be tested on a small recorded fixture
(``tests/fixtures/``) without a chip.  Busy time is the union of the
intervals in which an operation ran on a device plane; the traced window is
the benchmark's own ``cb.window`` host span, so device and host times are
read on the profiler's one clock.  Each idle gap is labelled by the
innermost ``cb.*`` host span around its midpoint: what the host was doing
while the device waited.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
from collections import defaultdict
from typing import Iterable, NamedTuple

DEVICE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "cb."
WINDOW_SPAN = "cb.window"
TOP = 10


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def extract(profile) -> list[Event]:
    """Device op events and the benchmark's host spans from a
    ``jax.profiler.ProfileData``."""
    out = []
    for plane in profile.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if dev and line.name != OP_LINE:
                continue
            for e in line.events:
                if dev or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce(events: Iterable[Event]) -> dict:
    """Busy and idle time of the device planes over the ``cb.window`` span.

    Returns ``busy_s`` (averaged over the device planes that ran an op),
    ``window_s``, ``idle_share``, ``device_ops`` (the op names that took
    most device time of their own, nested ops not counted twice) and
    ``idle_gaps`` (the longest gaps, each named by the host span around
    it)."""
    events = list(events)
    windows = [e for e in events if e.name == WINDOW_SPAN and not e.plane.startswith(DEVICE_PREFIX)]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = w.start_ns, w.start_ns + w.dur_ns
    ops: dict[str, list] = defaultdict(list)
    spans = []
    for e in events:
        s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        if e.plane.startswith(DEVICE_PREFIX):
            if t > s:
                ops[e.plane].append((s, t, op_name(e.name)))
        elif e.name != WINDOW_SPAN:
            spans.append(e)
    if not ops:
        raise ValueError("no device operation ran inside the traced window")
    op_time: dict[str, float] = defaultdict(float)
    busy, gaps = [], []
    for plane, iv in ops.items():
        for name, t in _self_times(iv):
            op_time[name] += t
        iv = [(s, t) for s, t, _n in iv]
        u = _union(iv)
        busy.append(sum(t - s for s, t in u))
        edges = [w0] + [x for se in u for x in se] + [w1]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t > s:
                gaps.append((t - s, s, t))
    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (w1 - w0) / 1e9
    gaps.sort(reverse=True)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [
            [n, t / 1e9]
            for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
        ],
        "idle_gaps": [
            [_label(spans, (s + t) / 2), d / 1e9] for d, s, t in gaps[:TOP]
        ],
    }


def op_name(hlo: str) -> str:
    """An op's HLO instruction name, without its shapes and operands."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _self_times(iv: list[tuple[float, float, str]]):
    """(name, time not covered by ops nested inside it) for each op on one
    plane: a loop's own event encloses its body's ops."""
    out, stack = [], []  # stack of [end, name, self time]
    for s, t, n in sorted(iv, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            out.append(stack.pop())
        if stack:
            stack[-1][2] -= min(t, stack[-1][0]) - s
        stack.append([t, n, t - s])
    out.extend(stack)
    return [(n, st) for _e, n, st in out]


def _label(spans: list[Event], at_ns: float) -> str:
    around = [e for e in spans if e.start_ns <= at_ns <= e.start_ns + e.dur_ns]
    if not around:
        return "outside any benchmark span"
    return min(around, key=lambda e: e.dur_ns).name


class Tracer:
    """Start and stop the JAX profiler around part of a run, into a
    temporary directory that is deleted once the trace is reduced.
    ``result`` holds ``reduce``'s result after ``stop``."""

    def __init__(self):
        self.result: dict = {}
        self._dir = None
        self._span = None

    @property
    def running(self) -> bool:
        return self._dir is not None

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="cbtrace")
        jax.profiler.start_trace(self._dir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        from jax.profiler import ProfileData

        try:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            paths = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"), recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            self.result = reduce(extract(ProfileData.from_file(paths[0])))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
