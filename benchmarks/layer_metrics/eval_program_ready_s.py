"""Orchestration (``fed/engine.py`` ``evaluate``): seconds the program's
calls that built or loaded a holdout-evaluation executable blocked
(``telemetry.compile_seconds{fn=engine.eval}``), over the run."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("telemetry.compile_seconds{fn=engine.eval}")
