"""What the program says of itself, for the readers beside this file: its
counters, and its spans placed on the device trace's clock.

The program's ``Tracer`` keeps its spans while a profiler session is open
(``telemetry/lifecycle.py``), with start and end on the process's monotonic
clock.  Each span is also a ``TraceAnnotation`` of the same name, so the
``fit`` events of the trace are the harness's and, nested in each, the
program's own.  The innermost are paired, in order, with the program's
``fit`` spans, and the spans under a ``fit`` are mapped through its two
ends.  The two durations differ by what lies between the two entries (a
clock reading; a few lines of Python where the program wrote no annotation)
and by the drift of one clock against the other: that bounds the error.

A program that has no such spans or counters (the parent of the PR that
added these readers) gives None everywhere, and nothing raises.
"""

from __future__ import annotations

import bisect
from typing import Optional

from benchmarks.harness import xplane

ROOT_SPAN = "fit"
MAX_ANCHOR_ERROR_NS = 100e3
NO_SPAN = "(no span)"

Interval = tuple[float, float]            # start_ns, end_ns
Placed = tuple[str, float, float]         # name, start_ns, end_ns on the trace


def counter(name: str) -> Optional[float]:
    """A counter or gauge of the program's registry by its snapshot key
    (``name{label=value}``); None if the program never touched it."""
    from colearn_federated_learning_tpu import telemetry

    value = telemetry.get_registry().snapshot().get(name)
    return float(value) if isinstance(value, (int, float)) else None


def counters(prefix: str) -> dict[str, float]:
    from colearn_federated_learning_tpu import telemetry

    return {key: float(value)
            for key, value in telemetry.get_registry().snapshot().items()
            if key.startswith(prefix) and isinstance(value, (int, float))}


def host_gaps(trace: xplane.Trace, ordinal: int
              ) -> tuple[list[Interval], float]:
    """The chip's idle intervals inside the window that are not wholly
    inside one execution of a compiled program, and the seconds of those
    that are.  Idle is the complement of the union of ``XLA Ops``, as
    ``device_idle_share`` takes it.  A module's event outlasts its last
    operation by a microsecond or so: a gap that begins there and ends
    after the module is the host's, all of it."""
    window = trace.window_ns
    device = trace.devices[ordinal]
    busy = xplane.busy_intervals(xplane.clip(device.ops, window))
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    modules = sorted(device.modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    gaps, inside_s = [], 0.0
    for start, end in zip(edges[::2], edges[1::2]):
        if end <= start:
            continue
        i = bisect.bisect_right(starts, start)
        if i and end <= modules[i - 1][1] + modules[i - 1][2]:
            inside_s += (end - start) / 1e9
        else:
            gaps.append((start, end))
    return gaps, inside_s


def placed_spans(trace: xplane.Trace
                 ) -> Optional[tuple[list[Placed], float]]:
    """The program's recorded spans on the trace's clock, and the largest
    difference in ns between a ``fit`` span's duration and its event's.
    None when they cannot be placed: none recorded, another count of
    ``fit`` spans than of innermost ``fit`` events, or a difference above
    ``MAX_ANCHOR_ERROR_NS``."""
    from colearn_federated_learning_tpu import telemetry

    spans = [s for s in telemetry.get_tracer().snapshot()
             if getattr(s, "end_ns", None) is not None]
    roots = [s for s in spans if s.name == ROOT_SPAN and s.parent_id is None]
    events = sorted(trace.spans, key=lambda e: e[1])
    innermost = [e for e, after in zip(events, events[1:] + [None])
                 if after is None or after[1] >= e[1] + e[2]]
    if not roots or len(roots) != len(innermost):
        return None
    placed, error = [], 0.0
    for root, (_, start, dur) in zip(roots, innermost):
        own = root.end_ns - root.start_ns
        error = max(error, abs(dur - own))
        if own <= 0 or error > MAX_ANCHOR_ERROR_NS:
            return None
        scale = dur / own
        placed.extend(
            (s.name, start + (s.start_ns - root.start_ns) * scale,
             start + (s.end_ns - root.start_ns) * scale)
            for s in spans if s.trace_id == root.trace_id)
    return placed, error


def split(gaps: list[Interval], placed: list[Placed]) -> dict[str, float]:
    """Seconds of the gaps by the innermost span open at each instant
    (spans nest, so that is the open span that started last);
    ``NO_SPAN`` where none is open."""
    totals: dict[str, float] = {}
    for lo, hi in gaps:
        over = [s for s in placed if s[1] < hi and s[2] > lo]
        cuts = sorted({lo, hi, *(t for s in over for t in s[1:]
                                 if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [s for s in over if s[1] <= mid < s[2]]
            name = max(open_, key=lambda s: s[1])[0] if open_ else NO_SPAN
            totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    return totals


def host_gap_split(r) -> Optional[dict[str, float]]:
    """The first chip's host-gap seconds in the traced window by program
    span; None without a device trace or without placeable spans."""
    if r.trace is None or not r.trace.devices:
        return None
    placed = placed_spans(r.trace)
    if placed is None:
        return None
    gaps, _ = host_gaps(r.trace, min(r.trace.devices))
    return split(gaps, placed[0])


def gap_ms_under(r, *names: str) -> Optional[float]:
    """Host-gap time under the named spans, in ms a round."""
    parts = host_gap_split(r)
    if parts is None or not r.rounds:
        return None
    return sum(parts.get(n, 0.0) for n in names) * 1e3 / r.rounds
