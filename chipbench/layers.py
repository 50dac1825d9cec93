"""A device trace split by the program's own host spans, named scopes and
XLA programs.

``chipbench.tracing`` reduces a trace to busy and idle time and names each
idle gap by the benchmark's ``cb.*`` spans.  This module reads the same
profile, on the same clock, for what the program marks itself:

- ``idle_by_span``: the device's idle seconds in the ``cb.window`` span,
  split by the innermost ``repro.*`` host span open at each instant
  (``none`` where none is open).  The parts add up to the idle total.
- ``busy_by_scope``: device self time by the innermost known
  ``jax.named_scope`` in each op's ``op_name``, read from the compiled
  HLO text of the programs (``other`` where none is known).
- ``busy_by_program``: device self time by XLA module.
- ``idle_gaps``: the longest gaps, each named by the innermost span of
  either prefix open at its midpoint.

Times are averaged over the device planes that ran an op, as
``tracing.reduce`` averages busy time.  Everything works on plain tuples,
so that it is tested on recorded fixtures without a chip;
``shares(result, kind)`` turns a result into per-layer percentages.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Iterable, NamedTuple

from chipbench.tracing import DEVICE_PREFIX, OP_LINE, TOP, WINDOW_SPAN, Event, _self_times, _union, op_name

MODULE_LINE = "XLA Modules"
SPAN_PREFIXES = ("repro.", "cb.")
PROGRAM_PREFIX = "repro."
SCOPES = ("embed", "norm", "attn", "mamba", "mlp", "moe", "head", "optimizer")
OTHER, NONE = "other", "none"
DECODE_PROGRAM = "jit_decode_step"


class Op(NamedTuple):
    plane: str
    name: str  # HLO instruction name
    start_ns: float
    dur_ns: float
    module: str  # XLA module, "" where unknown


def raw_events(profile) -> list[Event]:
    """Device ops and modules (the ``XLA Ops`` and ``XLA Modules`` lines),
    and host spans of either prefix, from a ``jax.profiler.ProfileData``.
    On a v5e an op event carries neither its ``op_name`` nor its module:
    scopes come from the compiled HLO text, modules from the module line."""
    out = []
    for plane in profile.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if dev and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for e in line.events:
                if dev or e.name.startswith(SPAN_PREFIXES):
                    out.append(Event(plane.name, line.name, e.name, float(e.start_ns), float(e.duration_ns)))
    return out


def _module(name: str) -> str:
    """A module's name without the program id that a trace may add."""
    return re.sub(r"\(\d+\)$", "", name)


def split(raw: Iterable[Event]) -> tuple[list[Event], list[Op]]:
    """(host spans, device ops), each op with the module of the ``XLA
    Modules`` event around its start on its plane."""
    raw = list(raw)
    spans = [Event(r.plane, r.line, r.name, r.start_ns, r.dur_ns)
             for r in raw if not r.plane.startswith(DEVICE_PREFIX)]
    modules = defaultdict(list)
    for r in sorted(raw, key=lambda r: r.start_ns):
        if r.line == MODULE_LINE:
            modules[r.plane].append((r.start_ns, r.start_ns + r.dur_ns, _module(r.name)))
    starts = {p: [s for s, _t, _m in ms] for p, ms in modules.items()}
    ops = []
    for r in raw:
        if not r.plane.startswith(DEVICE_PREFIX) or r.line != OP_LINE:
            continue
        i = bisect.bisect_right(starts.get(r.plane, []), r.start_ns) - 1
        s, t, m = modules[r.plane][i] if i >= 0 else (0.0, -1.0, "")
        ops.append(Op(r.plane, op_name(r.name), r.start_ns, r.dur_ns, m if s <= r.start_ns <= t else ""))
    return spans, ops


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_COMPONENT = re.compile(r"(?:(?:jvp|transpose|vmap|remat|checkpoint)\()*(\w+)\)*")


def hlo_paths(hlo_text: str) -> dict[tuple[str, str], str]:
    """{(module, instruction): op_name, "" where it has none} for every
    instruction of a compiled program's text (``compiled.as_text()``)."""
    m = re.match(r"HloModule ([^\s,]+)", hlo_text)
    module = _module(m.group(1)) if m else ""
    out = {}
    for name, rest in _INSTR.findall(hlo_text):
        path = _OP_NAME.search(rest)
        out[(module, name)] = path.group(1) if path else ""
    return out


def scope_of(path: str) -> str:
    """The innermost known scope in an ``op_name`` path: the last path
    component that is a scope's name, bare or inside transforms such as
    ``transpose(jvp(head))``.  A ``jit(...)`` component is a function, not a
    scope."""
    for comp in reversed(path.split(";")[0].split("/")):
        m = _COMPONENT.fullmatch(comp)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return OTHER


def _segments(spans: list[Event], w0: float, w1: float) -> list[tuple[float, float, str]]:
    """Pieces covering [w0, w1], each with the innermost (shortest) span
    open over all of it, ``NONE`` where none is."""
    cuts = {w0, w1}
    for e in spans:
        cuts.update(min(max(x, w0), w1) for x in (e.start_ns, e.start_ns + e.dur_ns))
    cuts = sorted(cuts)
    by_start = sorted(spans, key=lambda e: e.start_ns)
    out, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j].start_ns <= a:
            active.append(by_start[j])
            j += 1
        active = [e for e in active if e.start_ns + e.dur_ns >= b]
        out.append((a, b, min(active, key=lambda e: e.dur_ns).name if active else NONE))
    return out


def _split_by(intervals: list[tuple[float, float]], segs) -> dict[str, float]:
    """The length of sorted, disjoint intervals within each segment's name."""
    out: dict[str, float] = defaultdict(float)
    k = 0
    for s, t in intervals:
        while k < len(segs) and segs[k][1] <= s:
            k += 1
        i = k
        while i < len(segs) and segs[i][0] < t:
            a, b, name = segs[i]
            out[name] += min(b, t) - max(a, s)
            i += 1
    return out


def reduce_layers(spans: Iterable[Event], ops: Iterable[Op], hlo_texts: Iterable[str] = ()) -> dict:
    """Idle time by host span, busy time by scope and by program, and the
    longest gaps, over the ``cb.window`` span, in seconds; and
    ``ops_unmatched``, the ops in the window that no given HLO text holds
    (their time goes to ``other``)."""
    spans = [e for e in spans if e.name.startswith(SPAN_PREFIXES)]
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = w.start_ns, w.start_ns + w.dur_ns
    spans = [e for e in spans if e.name != WINDOW_SPAN]
    paths: dict = {}
    for text in hlo_texts:
        paths.update(hlo_paths(text))
    planes: dict[str, list] = defaultdict(list)
    for op in ops:
        s, t = max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1)
        if t > s:
            planes[op.plane].append((s, t, op))
    if not planes:
        raise ValueError("no device operation ran inside the traced window")
    program_segs = _segments([e for e in spans if e.name.startswith(PROGRAM_PREFIX)], w0, w1)
    all_segs = _segments(spans, w0, w1)
    idle: dict[str, float] = defaultdict(float)
    scope: dict[str, float] = defaultdict(float)
    program: dict[str, float] = defaultdict(float)
    gaps = []
    for iv in planes.values():
        for op, t in _self_times(iv):
            scope[scope_of(paths.get((op.module, op.name), ""))] += t
            program[op.module or OTHER] += t
        u = _union([(s, t) for s, t, _op in iv])
        edges = [w0] + [x for se in u for x in se] + [w1]
        holes = [(s, t) for s, t in zip(edges[0::2], edges[1::2]) if t > s]
        for name, t in _split_by(holes, program_segs).items():
            idle[name] += t
        gaps += [(t - s, s, t) for s, t in holes]
    n = len(planes) * 1e9
    gaps.sort(reverse=True)
    return {
        "window_s": (w1 - w0) / 1e9,
        "ops_unmatched": sum(1 for iv in planes.values() for _s, _t, op in iv
                             if (op.module, op.name) not in paths),
        "idle_by_span": {k: v / n for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "busy_by_scope": {k: v / n for k, v in sorted(scope.items(), key=lambda kv: -kv[1])},
        "busy_by_program": {k: v / n for k, v in sorted(program.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[_at(all_segs, (s + t) / 2), d / 1e9] for d, s, t in gaps[:TOP]],
    }


def clock_offset(raw: Iterable[Event], span: str, program: str) -> tuple[float, float]:
    """The shared-clock check: the range of shifts, in seconds, that put
    every run of ``program`` (its ``XLA Modules`` events) inside its own
    ``span`` host span, where each such span launches one run and waits for
    it.  Runs and spans pair in order; where the trace cut one off at an
    end, the pairing that needs the smallest shift is taken (the clocks
    are taken to disagree by less than the time between runs).  A range that
    holds 0 gives no sign of an offset between the host's clock and the
    device's; one above 0 means the device's times read early."""
    raw = list(raw)
    spans = sorted((r for r in raw if r.name == span and not r.plane.startswith(DEVICE_PREFIX)),
                   key=lambda r: r.start_ns)
    runs = sorted((r for r in raw if r.line == MODULE_LINE and _module(r.name) == program),
                  key=lambda r: r.start_ns)
    if not spans or not runs:
        raise ValueError(f"no {span} span or no {program} run in the trace")
    n = min(len(spans), len(runs))
    best = None
    for i in range(len(spans) - n + 1):
        for j in range(len(runs) - n + 1):
            pairs = list(zip(spans[i:i + n], runs[j:j + n]))
            lo = max(c.start_ns - m.start_ns for c, m in pairs)
            hi = min(c.start_ns + c.dur_ns - m.start_ns - m.dur_ns for c, m in pairs)
            if best is None or max(abs(lo), abs(hi)) < max(abs(best[0]), abs(best[1])):
                best = (lo, hi)
    return best[0] / 1e9, best[1] / 1e9


def _at(segs, x: float) -> str:
    i = bisect.bisect_right([a for a, _b, _n in segs], x) - 1
    return segs[max(i, 0)][2]


def shares(result: dict, kind: str) -> dict[str, float]:
    """The per-layer percentages of a cell's split: for the backlog
    (``serve_closed``) the idle time in the sampling, logits-fetch and
    refill spans over the window, and the device time of every program but
    the decode step (prefill, row scatter, empty row caches) over busy time;
    for a train cell each scope's device self time over busy time."""
    idle, scope = result["idle_by_span"], result["busy_by_scope"]
    busy = sum(scope.values())
    if kind == "serve_closed":
        w = result["window_s"]
        serve = lambda *names: 100.0 * sum(idle.get(f"repro.serve.{n}", 0.0) for n in names) / w
        return {
            "idle_in_sample.backlog": serve("sample"),
            "idle_in_fetch.backlog": serve("fetch"),
            "idle_in_refill.backlog": serve("refill", "prefill", "insert_row"),
            "refill_device_share.backlog":
                100.0 * (busy - result["busy_by_program"].get(DECODE_PROGRAM, 0.0)) / busy,
        }
    out = {
        "mixer_share.train": 100.0 * (scope.get("attn", 0.0) + scope.get("mamba", 0.0)) / busy,
        "head_share.train": 100.0 * (scope.get("embed", 0.0) + scope.get("head", 0.0)) / busy,
        "optimizer_share.train": 100.0 * scope.get("optimizer", 0.0) / busy,
    }
    if scope.get("mlp"):
        out["mlp_share.train"] = 100.0 * scope["mlp"] / busy
    return out
