"""Round-scoped telemetry lifecycle: span-trace window + jax profiler.

One object owns BOTH per-round observability mechanisms so they share a
lifecycle (open before the round, settle after it, flush on close, even
on an exception mid-round):

- the span tracer window: spans are kept in memory with
  ``RunConfig.trace_dir`` set (and written as Chrome-trace JSON;
  ``trace_rounds`` > 0 limits recording to the first N rounds the
  lifecycle sees, 0 = all rounds) *or* while a jax profiler session is
  open, whoever opened it — the spans are on that profile's host plane as
  annotations either way (``Tracer.span``), and the buffer gives whoever
  reads the profile their parents and attributes.  With neither, nothing
  is kept;
- the ``jax.profiler`` window (``RunConfig.profile_dir``):
  :class:`RoundProfiler`, which leaves the programs' scope tables beside
  the profile when the call ends.

``engine.fit`` drives ``before_round``/``after_round``/``end_round``/
``close``.  The coordinators, workers and fleetsim hold tracers of their
own with their own recording rule; their spans are annotations too.
"""

from __future__ import annotations

from typing import Optional

from colearn_federated_learning_tpu.telemetry import export, registry
from colearn_federated_learning_tpu.telemetry.tracer import (
    Tracer,
    profiler_session_open,
)


class RoundProfiler:
    """Start/stop a jax profiler trace around a window of rounds — by
    default rounds 1..2, skipping round 0 so compile time doesn't drown
    the steady state — writing to ``RunConfig.profile_dir`` (CLI
    ``--profile-dir``), viewable in TensorBoard or Perfetto."""

    def __init__(self, profile_dir: Optional[str], first_round: int = 1,
                 num_rounds: int = 2):
        self.profile_dir = profile_dir
        self.first = first_round
        self.last = first_round + num_rounds - 1
        self._active = False
        self._scopes_due = False

    @property
    def active(self) -> bool:
        """Whether this object's jax trace window is currently open."""
        return self._active

    def before_round(self, round_idx: int) -> None:
        if self.profile_dir and not self._active and round_idx == self.first:
            import jax

            jax.profiler.start_trace(self.profile_dir)
            self._active = True

    def after_round(self, round_idx: int) -> None:
        if self._active and round_idx >= self.last:
            self.close()

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._scopes_due = True

    def write_scopes(self) -> Optional[str]:
        """Once a window has closed, the programs' scope tables beside its
        profile (``program_scopes.json``), for ``colearn trace-summary
        <profile_dir>``.  It compiles or loads each program once more, so
        it is ``RoundTelemetry.close`` that calls it, after the last
        round, and never ``after_round``."""
        if not self._scopes_due:
            return None
        self._scopes_due = False
        return export.write_program_scopes(self.profile_dir)


class RoundTelemetry:
    """Drive the trace window and the jax profiler window together, for
    one ``fit()`` call.

    The tracer may be one that several learners record into in turn (the
    process-wide default): ``owner`` is the learner's token.  A recording
    call appends to the buffer if the buffer is its owner's and starts it
    afresh if not; a call that records nothing empties it.  So the buffer
    holds the newest recording window of one learner and nothing
    accumulates outside one.
    """

    def __init__(self, run_config, tracer: Tracer,
                 owner: Optional[object] = None):
        self.tracer = tracer
        self.trace_dir: Optional[str] = getattr(run_config, "trace_dir", None)
        self.trace_rounds: int = getattr(run_config, "trace_rounds", 0) or 0
        self.run_name: str = getattr(run_config, "name", "default")
        self.profiler = RoundProfiler(getattr(run_config, "profile_dir", None))
        self._first_round: Optional[int] = None
        self._written: Optional[str] = None
        # Looked at once per fit(): a session opened by the caller (the
        # benchmark's traced window).  One that --profile-dir opens later,
        # inside this call, still gets the annotations.
        record = bool(self.trace_dir) or profiler_session_open()
        if not record or tracer.owner is not owner:
            tracer.clear()
        tracer.owner = owner if record else None
        tracer.enabled = record

    def before_round(self, round_idx: int) -> None:
        self.profiler.before_round(round_idx)
        if not self.trace_dir:
            return
        if self._first_round is None:
            self._first_round = round_idx
        if self.trace_rounds:
            in_window = round_idx - self._first_round < self.trace_rounds
            self.tracer.enabled = in_window

    def after_round(self, round_idx: int) -> None:
        """Profiler half — call while the round's device work is settled,
        still inside the round span."""
        self.profiler.after_round(round_idx)

    def end_round(self, round_idx: int) -> None:
        """Trace-window half — call AFTER the round span has closed, so
        an early flush includes the final traced round."""
        if (self.trace_dir and self.trace_rounds
                and self._first_round is not None
                and round_idx - self._first_round == self.trace_rounds - 1):
            # The window just closed: flush now, so a long run yields its
            # trace file without waiting for the final round.
            self.write()

    def write(self) -> Optional[str]:
        if not self.trace_dir:
            return None
        self._written = export.write_tracer(
            self.trace_dir, self.run_name, self.tracer,
            metrics=registry.get_registry().snapshot(),
        )
        return self._written

    def close(self) -> Optional[str]:
        """Settle both windows.  Safe under mid-round exceptions — the
        process-global jax profiler must never be left running, and
        whatever spans were recorded still reach disk.  Recording ends
        with the call: what the process does between two ``fit()`` calls
        is not kept."""
        self.profiler.close()
        self.profiler.write_scopes()
        if self.trace_dir and (self._written is None or self.tracer.enabled):
            self.write()
        self.tracer.enabled = False
        return self._written
