"""From a profiler trace to numbers: device busy and idle time, device
time per operation, collective time and the part of it nothing hides, and
the longest idle gaps by what the host was doing.

The input is a plain structure, so that the arithmetic can be checked on a
recorded fixture without a chip::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]},
                {"name": "/host:CPU", "lines": [...]}]}

``from_xplane`` makes it from the ``.xplane.pb`` that ``jax.profiler``
writes (read with ``jax.profiler.ProfileData``, nothing else).

What the planes are under jax 0.9.0 / libtpu 0.0.34 (looked at by hand in
PR 22, PERF.md section 5): one plane ``/device:TPU:<n>`` per chip whose
line ``XLA Ops`` holds one event per executed HLO operation under XLA's
own instruction text (``%fusion.123 = bf16[...] fusion(...)``; the name
before `` = `` is kept), with control-flow operations (``while``) as
events that CONTAIN their bodies' events; ``Async XLA Ops`` holds the
asynchronous copies, which overlap the former and are not counted;
``XLA Modules`` holds one event per executable run and ``Steps`` one per
annotated step.  ``/host:CPU`` holds one line per host thread with the
``cb:`` and ``pt:`` annotations, on the same clock as the device planes
(a window's first operation starts 0.65 ms after its ``cb:window``).
Busy time is the union of the work events of ``XLA Ops``: all but the
containers, which alone would make the device look 100 % busy.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPAN = re.compile(r"^(cb|pt):")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")

Interval = Tuple[float, float]            # [start_ns, end_ns)


def from_xplane(path: str) -> dict:
    """The plain structure above from an ``.xplane.pb`` file; keeps the
    device planes whole and, of the host plane, the cb:/pt: spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if is_device or HOST_SPAN.match(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of ``a`` that no interval of ``b`` covers (both disjoint
    and sorted, as ``union`` returns them)."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def work_events(events: Sequence[Sequence]) -> List[Sequence]:
    """The events of one line that are work on the device: all but the
    containers.  A control-flow operation's event spans its body's events,
    and counting it would make the device look busy throughout; an event
    is taken for a container when the events directly inside it cover more
    than half of it (a fusion that merely contains the few nanoseconds of
    an asynchronous copy's issue stays work)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    covered = [0.0] * len(order)
    stack: List[int] = []                 # indices of still-open events
    for i, (_, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        for j in reversed(stack):         # the innermost that contains it
            if start + dur <= order[j][1] + order[j][2]:
                covered[j] += dur
                break
        stack.append(i)
    return [e for e, c in zip(order, covered) if c <= 0.5 * e[2]]


def short_name(name: str) -> str:
    """XLA's instruction name out of the event's text:
    '%fusion.12 = f32[8]{0} fusion(...)' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%").strip() or name


@dataclasses.dataclass
class TraceSummary:
    window_s: float                  # length of the traced window
    busy_s: float                    # device busy, averaged over chips
    collective_s: float              # in collectives, on the first chip
    collective_exposed_s: float      # ... while no other op ran there
    steps: Optional[int]             # training steps in the window
    ops: Dict[str, float]            # seconds by XLA name, first chip
    gaps: List[Tuple[str, float]]    # idle gaps by enclosing host span
    devices: int

    def top_ops(self, n: int) -> List[list]:
        return [[k, v] for k, v in sorted(
            self.ops.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> List[list]:
        return [[k, v] for k, v in sorted(
            self.gaps, key=lambda kv: -kv[1])[:n]]


def _host_spans(trace: dict) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if HOST_SPAN.match(name):
                    spans.append((name, start, start + dur))
    return spans


def _enclosing(spans, start: float, end: float) -> str:
    """The innermost (shortest) cb:/pt: host span that covers the middle
    of [start, end); 'between spans' if none does."""
    mid = 0.5 * (start + end)
    best = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "between spans"


def summarize(trace: dict, n_devices: int, steps: Optional[int] = None,
              window_span: str = "cb:window") -> TraceSummary:
    """Reduce one traced window.

    The window runs from the start of the first ``window_span`` host span
    to the end of the last one; without such spans, from the first device
    operation to the last.  Busy time is clipped to it."""
    devices = sorted((p for p in trace["planes"]
                      if DEVICE_PLANE.match(p["name"])),
                     key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1))
                     )[:n_devices]
    per_device = []
    for plane in devices:
        events = [e for line in plane["lines"] if line["name"] == OPS_LINE
                  for e in line["events"]]
        per_device.append(work_events(events))
    if not any(per_device):
        return TraceSummary(0.0, 0.0, 0.0, 0.0, steps, {}, [], len(devices))

    spans = _host_spans(trace)
    marks = [(s, e) for name, s, e in spans if name == window_span]
    if marks:
        w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        w0 = min(e[1] for evs in per_device for e in evs)
        w1 = max(e[1] + e[2] for evs in per_device for e in evs)

    def clip(evs):
        return [(max(s, w0), min(s + d, w1)) for _, s, d in evs
                if s < w1 and s + d > w0]

    busy = [union(clip(evs)) for evs in per_device]
    busy_s = sum(total(b) for b in busy) / len(busy) * 1e-9

    first = [(short_name(n), s, d) for n, s, d in per_device[0]
             if s < w1 and s + d > w0]
    ops: Dict[str, float] = {}
    for name, s, d in first:
        ops[name] = ops.get(name, 0.0) + (min(s + d, w1) - max(s, w0)) * 1e-9
    coll = union(clip([e for e in first if COLLECTIVE.match(e[0])]))
    other = union(clip([e for e in first if not COLLECTIVE.match(e[0])]))
    exposed = subtract(coll, other)

    gaps: Dict[str, float] = {}
    idle = subtract([(w0, w1)], busy[0])
    for s, e in idle:
        key = _enclosing(spans, s, e)
        gaps[key] = gaps.get(key, 0.0) + (e - s) * 1e-9
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_s,
        collective_s=total(coll) * 1e-9,
        collective_exposed_s=total(exposed) * 1e-9, steps=steps, ops=ops,
        gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
        devices=len(devices))
