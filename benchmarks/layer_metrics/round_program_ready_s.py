"""Round program: seconds the program's calls that built or loaded a round
executable blocked (``telemetry.compile_seconds{fn=engine.round}``:
tracing, compile or cache load, enqueue), over the run; all of it is
set-up unless the run is not ``correct``."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("telemetry.compile_seconds{fn=engine.round}")
