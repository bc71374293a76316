"""Orchestration (``telemetry/runtime.py`` ``CompileTracker.scopes``): what
the scope tables cost this process, in seconds, the sum of the program's
``telemetry.scope_table_seconds{fn}``: a load of each executable's text or,
where the compile cache held one under older names, one build.  Paid once,
after the window, by a traced run alone; nothing asks for a table before
the first by-scope reader does."""

from benchmarks.layer_metrics import _program, _scopes


def read(r):
    if _scopes.split(r) is None:
        return None
    return _program.counter("telemetry.scope_table_seconds")
