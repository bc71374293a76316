"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer metrics and the result line read.

Read with ``jax.profiler.ProfileData`` and nothing else.  What the
reduction relies on, checked by hand on a trace from the v5e (see
``benchmarks/tests/data``):

- one plane per chip, named ``/device:TPU:<n>``;
- on it a line ``XLA Ops`` whose events are the HLO operations that ran,
  named by their whole instruction text (``%fusion.12 = (f32[...]...``;
  kept here as ``fusion.12 bf16[128,32,32,32,64]``, the name and the
  largest array the instruction touches), nested where an
  operation (a ``while``, a ``call``) contains others, and a line ``XLA
  Modules`` with one event per execution of a compiled program, named
  ``jit_<function>(<fingerprint>)``;
- the harness's own ``TraceAnnotation`` spans on a line of the
  ``/host:CPU`` plane, on the same clock.

Times are nanoseconds as the trace gives them; results are seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import math
import re
import statistics
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")

Event = tuple[str, float, float]          # name, start_ns, duration_ns


@dataclasses.dataclass
class Device:
    ops: list[Event]
    modules: list[Event]


@dataclasses.dataclass
class Trace:
    devices: dict[int, Device]            # by chip ordinal
    spans: list[Event]                    # the harness's annotations

    @property
    def window_ns(self) -> tuple[float, float]:
        """From the first harness span's start to the last one's end;
        without spans, the extent of the device events."""
        events = self.spans or [
            e for d in self.devices.values() for e in d.ops]
        if not events:
            return (0.0, 0.0)
        return (min(e[1] for e in events), max(e[1] + e[2] for e in events))

    @property
    def window_s(self) -> float:
        start, end = self.window_ns
        return (end - start) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds inside the window in which an operation ran, averaged
        over the chips; 0 when the trace has no device plane."""
        window = self.window_ns
        return statistics.fmean(
            [busy_s(clip(d.ops, window)) for d in self.devices.values()]
            or [0.0])


def load(path: str, span_names: Iterable[str]) -> Trace:
    from jax.profiler import ProfileData

    span_names = set(span_names)
    devices: dict[int, Device] = {}
    spans: list[Event] = []
    for plane in ProfileData.from_file(path).planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            devices[int(match.group(1))] = Device(
                ops=_events(lines.get(OPS_LINE)),
                modules=_events(lines.get(MODULES_LINE)))
        elif plane.name == HOST_PLANE:
            # The host plane can hold millions of events; keep the spans.
            spans.extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for line in plane.lines for e in line.events
                if e.name in span_names)
    spans.sort(key=lambda e: e[1])
    return Trace(devices=devices, spans=spans)


def _events(line) -> list[Event]:
    if line is None:
        return []
    labels: dict[str, str] = {}
    out = []
    for e in line.events:
        text = e.name
        if text not in labels:
            labels[text] = op_label(text)
        out.append((labels[text], float(e.start_ns), float(e.duration_ns)))
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def op_label(text: str) -> str:
    """``%fusion.12 = (f32[64]{0}, bf16[128,32,32,64]{...}) fusion(...)``
    -> ``fusion.12 bf16[128,32,32,64]``: an operation's name says little,
    the largest array it reads or writes says which layer it belongs to.
    A name that is no instruction text is kept as it is."""
    name, _, rest = text.partition(" = ")
    largest, size = "", -1
    for dtype, dims in ARRAY.findall(rest):
        n = math.prod(int(d) for d in dims.split(",") if d)
        if n > size:
            largest, size = f" {dtype}[{dims}]", n
    return name.lstrip("%") + largest


def clip(events: list[Event], window: tuple[float, float]) -> list[Event]:
    """Events cut to the window; those outside it dropped."""
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def busy_intervals(events: list[Event]) -> list[tuple[float, float]]:
    """Union of the events' intervals, in order."""
    merged: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(s, e) for s, e in merged]


def busy_s(events: list[Event]) -> float:
    return sum(e - s for s, e in busy_intervals(events)) / 1e9


def self_times(events: list[Event]) -> dict[str, float]:
    """Seconds by operation name, a container's time less its children's,
    so that a ``while`` does not count its body twice."""
    own = [dur for _, _, dur in events]
    stack: list[tuple[float, int]] = []        # (end_ns, index)
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    for i in order:
        _, start, dur = events[i]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur
        stack.append((start + dur, i))
    totals: dict[str, float] = {}
    for (name, _, _), t in zip(events, own):
        totals[name] = totals.get(name, 0.0) + max(t, 0.0) / 1e9
    return totals


def module_name(event_name: str) -> str:
    """``jit_round_fn(1234)`` -> ``jit_round_fn``."""
    return event_name.split("(", 1)[0]


def idle_gaps(trace: Trace, ordinal: int) -> dict[str, float]:
    """Idle seconds on one chip inside the window, by what was going on
    when the gap began: the harness span that was open (``between_chunks``
    when none was), and the program that was running (``inside``: the
    device waits between two of its own operations) or had run last
    (``after``: the device waits for the host)."""
    window = trace.window_ns
    device = trace.devices[ordinal]
    busy = busy_intervals(clip(device.ops, window))
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    span_starts = [s[1] for s in trace.spans]
    module_starts = [m[1] for m in device.modules]
    totals: dict[str, float] = {}
    for i in range(0, len(edges), 2):
        start, end = edges[i], edges[i + 1]
        if end <= start:
            continue
        span = _latest(trace.spans, span_starts, start)
        inside = span is not None and start < span[1] + span[2]
        module = _latest(device.modules, module_starts, start)
        if module is None:
            where = "before any program"
        else:
            running = start < module[1] + module[2]
            where = (f"{'inside' if running else 'after'} "
                     f"{module_name(module[0])}")
        label = f"{span[0] if inside else 'between_chunks'}:{where}"
        totals[label] = totals.get(label, 0.0) + (end - start) / 1e9
    return totals


def _latest(events: list[Event], starts: list[float],
            t: float) -> Optional[Event]:
    """The last of the (sorted) events to start at or before ``t``."""
    i = bisect.bisect_right(starts, t)
    return events[i - 1] if i else None


def top(totals: dict[str, float], n: int = 10) -> list[list]:
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]
