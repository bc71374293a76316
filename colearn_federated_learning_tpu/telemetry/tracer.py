"""Dependency-free span tracer for federated rounds.

The reference logs accuracy with prints/CSV and has no tracing (PAPER.md
§5); the only timing signal in the rebuild so far was whole-round wall
time plus a 2-round ``jax.profiler`` window.  This tracer answers *where*
a round spends its time: nested spans with monotonic-clock durations and
wall-clock anchors, cheap enough to leave on in production paths.

Where jax is installed every span is also a ``jax.profiler``
``TraceAnnotation`` of the same name, so in any jax profile the spans sit
on the ``/host:CPU`` plane on the profiler's own clock, beside the device's
operations (≈ 0.3 µs a span while no profiler session is open).  The
import is made on first use; without jax the spans are timed all the same.

Design points:

- ``tracer.span("aggregate", round=3)`` is a context manager; nesting is
  tracked per thread, so spans opened inside a fan-out worker thread do
  not accidentally parent onto the coordinator's round span.
- The context manager ALWAYS yields a timed :class:`Span` — even when the
  tracer is disabled — so hot paths can read ``sp.duration_s`` for
  metrics (JSONL phase fields) without a second clock read; only the
  *recording* into the in-memory buffer is gated on ``enabled``.
- Spans carry ``(trace_id, span_id, parent_id)``; ``current_context()``
  exports the active identity for wire propagation and ``span(parent=…)``
  adopts a remote parent, which is how a worker's local-train span
  stitches under the coordinator's round span across processes.
- Cross-process stitching is completed by ``Span.to_dict`` /
  ``Tracer.adopt``: a worker ships its finished spans back in the reply
  metadata and the coordinator adopts them into its own buffer.

Wall-clock (``time.time``) anchors position spans on a shared timeline
across processes on one machine; start, end and duration always come from
``time.perf_counter_ns`` so individual spans are immune to clock steps.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Iterator, Optional

SpanContext = tuple[str, str]            # (trace_id, span_id)

_id_counter = itertools.count(1)
_id_lock = threading.Lock()


@functools.cache
def _annotation():
    """``jax.profiler.TraceAnnotation``, or a context manager that does
    nothing where jax is absent."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return contextlib.nullcontext
    return TraceAnnotation


def profiler_session_open() -> bool:
    """Whether a jax profiler session is recording in this process."""
    is_enabled = getattr(_annotation(), "is_enabled", None)
    return bool(is_enabled is not None and is_enabled())


def new_id() -> str:
    """Process-unique 64-bit-style hex id (pid-salted so ids minted by a
    coordinator and an in-process loopback worker never collide)."""
    with _id_lock:
        n = next(_id_counter)
    return f"{os.getpid() & 0xFFFF:04x}{n & 0xFFFFFFFFFFFF:012x}"


@dataclass
class Span:
    """One timed operation.  ``t_wall`` anchors the span on the shared
    wall-clock timeline; ``start_ns``/``end_ns`` are this process's
    monotonic clock (``time.perf_counter_ns``) and ``duration_s`` their
    difference."""

    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str] = None
    process: str = "main"
    t_wall: float = 0.0                  # epoch seconds at start
    attrs: dict = field(default_factory=dict)
    start_ns: int = 0                    # perf_counter_ns at start
    end_ns: Optional[int] = None         # perf_counter_ns at end

    @property
    def ended(self) -> bool:
        return self.end_ns is not None

    @property
    def duration_s(self) -> float:
        end = self.end_ns if self.end_ns is not None else (
            time.perf_counter_ns())
        return (end - self.start_ns) / 1e9

    @property
    def context(self) -> SpanContext:
        return (self.trace_id, self.span_id)

    def to_dict(self) -> dict:
        """JSON-safe wire form (worker reply metadata / trace files)."""
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "process": self.process,
            "t_wall": self.t_wall,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "duration_s": self.duration_s,
            "attrs": dict(self.attrs),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        sp = cls(
            name=d["name"], trace_id=d["trace_id"], span_id=d["span_id"],
            parent_id=d.get("parent_id"), process=d.get("process", "main"),
            t_wall=float(d.get("t_wall", 0.0)), attrs=dict(d.get("attrs", {})),
        )
        # A form without the clock readings (a loaded trace file) keeps
        # its duration.
        sp.start_ns = int(d.get("start_ns") or 0)
        end_ns = d.get("end_ns")
        sp.end_ns = int(end_ns) if end_ns is not None else sp.start_ns + (
            round(float(d.get("duration_s", 0.0)) * 1e9))
        return sp


class Tracer:
    """Per-component span recorder (engine, coordinator, one per worker).

    ``enabled`` gates recording only — ``span()`` always times.  The
    buffer is bounded by ``max_spans``; once full, new spans are dropped
    and counted in ``dropped`` (a trace that silently swallows its own
    overflow would misreport coverage).  ``owner`` says whose spans the
    buffer holds, for a tracer that several components record into in
    turn (``RoundTelemetry`` sets it).
    """

    def __init__(self, process: str = "main", enabled: bool = True,
                 max_spans: int = 100_000):
        self.process = process
        self.enabled = enabled
        self.max_spans = max_spans
        self.spans: list[Span] = []
        self.dropped = 0
        self.owner: Optional[object] = None
        self._lock = threading.Lock()
        self._local = threading.local()

    # -- per-thread span stack -----------------------------------------
    def _stack(self) -> list[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_context(self) -> Optional[SpanContext]:
        """(trace_id, span_id) of this thread's innermost open span —
        the identity to inject into outbound messages."""
        stack = self._stack()
        return stack[-1].context if stack else None

    @contextmanager
    def span(self, name: str, parent: Optional[SpanContext] = None,
             **attrs) -> Iterator[Span]:
        """Open a span.  ``parent`` overrides the thread-local nesting
        with an explicit (possibly remote) parent context."""
        stack = self._stack()
        if parent is not None:
            trace_id, parent_id = parent
        elif stack:
            trace_id, parent_id = stack[-1].trace_id, stack[-1].span_id
        else:
            trace_id, parent_id = new_id(), None
        sp = Span(name=name, trace_id=trace_id, span_id=new_id(),
                  parent_id=parent_id, process=self.process,
                  t_wall=time.time(), attrs=attrs)
        stack.append(sp)
        try:
            # The annotation opens just before the span's clock is read
            # and closes just after, so the two agree to the cost of one
            # clock reading.
            with _annotation()(name):
                sp.start_ns = time.perf_counter_ns()
                try:
                    yield sp
                finally:
                    sp.end_ns = time.perf_counter_ns()
        finally:
            stack.pop()
            self._record(sp)

    def _record(self, sp: Span) -> None:
        sink = getattr(self._local, "capture", None)
        if sink is not None:
            sink.append(sp)
        if not self.enabled:
            return
        with self._lock:
            if len(self.spans) < self.max_spans:
                self.spans.append(sp)
            else:
                self.dropped += 1

    @contextmanager
    def capture(self) -> Iterator[list[Span]]:
        """Additionally collect every span FINISHED on this thread while
        active — how a worker gathers the spans of one request to ship
        them back to the coordinator, without draining the shared
        buffer under concurrent requests."""
        prev = getattr(self._local, "capture", None)
        captured: list[Span] = []
        self._local.capture = captured
        try:
            yield captured
        finally:
            self._local.capture = prev

    # -- cross-process stitching ---------------------------------------
    def adopt(self, span_dicts: list, process: Optional[str] = None) -> int:
        """Ingest remote spans (``Span.to_dict`` forms) into this buffer;
        returns how many were adopted.  Malformed entries are skipped —
        a peer must not be able to kill the coordinator's trace."""
        adopted = 0
        for d in span_dicts or []:
            try:
                sp = Span.from_dict(d)
            except (KeyError, TypeError, ValueError):
                continue
            if process is not None:
                sp.process = process
            with self._lock:
                if len(self.spans) < self.max_spans:
                    self.spans.append(sp)
                    adopted += 1
                else:
                    self.dropped += 1
        return adopted

    def snapshot(self) -> list[Span]:
        with self._lock:
            return list(self.spans)

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.dropped = 0


_declared_scopes: set[str] = set()


def device_scope(name: str):
    """``jax.named_scope(name)``, for the parts of a compiled program whose
    device time has a reader: the name goes into the ``op_name`` of every
    operation traced under it (debug information only: the lowered text
    and the default compile-cache key are what they were), and into
    ``declared_scopes()``, which the scope table of ``telemetry/runtime.py``
    checks an executable's names against.  Trace time only: a running
    program never passes here.  The vocabulary is in README, Observability."""
    import jax

    _declared_scopes.add(name)
    return jax.named_scope(name)


def declared_scopes() -> frozenset[str]:
    """Every name a ``device_scope`` was entered under in this process."""
    return frozenset(_declared_scopes)


_default_tracer = Tracer(process="main", enabled=False)


def get_tracer() -> Tracer:
    """Process-wide default tracer: the engine's, so that whoever opened a
    recording window (``RoundTelemetry``) finds its spans from anywhere in
    the process.  Records nothing until a window enables it.  Components
    that want isolation (the coordinators, each worker, fleetsim) hold
    their own instance instead."""
    return _default_tracer
