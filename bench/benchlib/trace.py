"""Reduction from a profiler trace to device busy time, op times and idle gaps.

A run with ``--trace 1`` wraps its traced window in the host span
``bench.window`` and every call it makes into a layer of the program in a
span of its own (``gen.submit``, ``serve.fetch``, ...), all written with
``jax.profiler.TraceAnnotation`` into the profiler's own trace, so host
spans and device events share one clock.

* busy: the union of the intervals in which an op ran on a device, clipped
  to the window, averaged over the devices that ran anything;
* op and module times: summed device durations by name (``XLA Ops`` and
  ``XLA Modules`` lines of each device plane);
* idle gaps: the window minus the busy union of the first device, each gap
  named by the benchmark span that overlaps it most (the inner one on a tie).

An op that runs a loop (an XLA ``while``) is one event whose time includes
the ops of its body, which are events of their own: op times overlap, the
busy union does not.
"""

from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans the benchmark writes (see the drivers)
SPAN_RE = re.compile(r"(gen|proxy|serve|s3|bench)\.[a-z_.]+")
_MODULE_ID = re.compile(r"\(\d+\)$")
#: an XLA op event is named by its HLO text: "%name = type opcode(operands)..."
_HLO = re.compile(r"%?([\w.\-]+) = (\S+?)(?:\{\S*\})? ([\w\-]+)\(")


def op_label(name: str) -> str:
    """A short label for an XLA op event: "name opcode type"."""
    m = _HLO.match(name)
    if m:
        return f"{m.group(1)} {m.group(3)} {m.group(2)}"
    return name.split(" = ", 1)[0].lstrip("%")[:80]


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and not plane.startswith("/device:CPU")


def events_from_xspace(path: str | Path) -> list[Event]:
    """Every event of an ``.xplane.pb`` file (read with JAX alone)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns))
            for plane in data.planes for line in plane.lines for ev in line.events]


def events_to_json(events: list[Event], path: str | Path) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def events_from_json(path: str | Path) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(ev: Event, w0: float, w1: float) -> tuple[float, float] | None:
    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
    return (s, e) if e > s else None


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the devices that ran an op
    devices: int
    op_s: dict[str, float]  # op label -> summed device seconds
    module_s: dict[str, float]  # module name (id stripped) -> summed seconds
    module_n: dict[str, int]  # module name -> event count
    idle_by_span: dict[str, float]  # host span name -> idle seconds under it
    ops: list[Event]  # device op events, clipped to the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> tuple[float, int]:
        """Summed seconds and count of op events whose HLO text matches."""
        rx = re.compile(pattern)
        tot, n = 0.0, 0
        for ev in self.ops:
            if rx.search(ev.name):
                tot += ev.dur_ns * 1e-9
                n += 1
        return tot, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_events(events: list[Event], window_span: str = WINDOW_SPAN) -> TraceSummary:
    wins = [e for e in events if not is_device_plane(e.plane) and e.name == window_span]
    if not wins:
        raise ValueError(f"trace has no host span {window_span!r}")
    w0, w1 = wins[0].start_ns, wins[0].end_ns
    if w1 <= w0:
        raise ValueError("the traced window has no length")

    per_dev: dict[str, list] = defaultdict(list)
    ops: list[Event] = []
    op_s: dict[str, float] = defaultdict(float)
    module_s: dict[str, float] = defaultdict(float)
    module_n: dict[str, int] = defaultdict(int)
    for ev in events:
        if not is_device_plane(ev.plane):
            continue
        iv = _clip(ev, w0, w1)
        if iv is None:
            continue
        if ev.line == OPS_LINE:
            per_dev[ev.plane].append(iv)
            clipped = dataclasses.replace(ev, start_ns=iv[0], dur_ns=iv[1] - iv[0])
            ops.append(clipped)
            op_s[op_label(ev.name)] += clipped.dur_ns * 1e-9
        elif ev.line == MODULES_LINE:
            name = _MODULE_ID.sub("", ev.name)
            module_s[name] += (iv[1] - iv[0]) * 1e-9
            module_n[name] += 1

    busy = {p: _union(ivs) for p, ivs in per_dev.items()}
    busy_s = (sum(sum(e - s for s, e in b) for b in busy.values()) / len(busy) * 1e-9
              if busy else 0.0)

    # Idle gaps on the first device, named by the host span overlapping most.
    first = busy[sorted(busy)[0]] if busy else []
    gaps, cur = [], w0
    for s, e in first:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    spans = [e for e in events if not is_device_plane(e.plane)
             and e.name != window_span and SPAN_RE.fullmatch(e.name)]
    spans.sort(key=lambda e: e.start_ns)
    idle: dict[str, float] = defaultdict(float)
    active: list[Event] = []  # spans begun before the gap's end, not yet over
    nxt = 0
    for g0, g1 in gaps:  # gaps come in time order
        while nxt < len(spans) and spans[nxt].start_ns < g1:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp.end_ns > g0]
        best, best_ov = "none", 0.0
        for sp in active:  # in start order: on a tie the inner span wins
            ov = min(sp.end_ns, g1) - max(sp.start_ns, g0)
            if ov > 0 and ov >= best_ov:
                best, best_ov = sp.name, ov
        idle[best] += (g1 - g0) * 1e-9

    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy_s, devices=len(busy),
                        op_s=dict(op_s), module_s=dict(module_s),
                        module_n=dict(module_n), idle_by_span=dict(idle), ops=ops)
