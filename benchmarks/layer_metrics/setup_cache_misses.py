"""Round program and the rest of the program's executables: how many the
run built and wrote to the persistent cache instead of loading (sum of
``telemetry.cache_miss_total{fn=engine.*}``: the learner's construction,
the round program, the evaluation).  0 on a warm cache; a program that
does not count them gives None."""

from benchmarks.layer_metrics import _program


def read(r):
    if _program.counter("telemetry.compile_seconds{fn=engine.round}") is None:
        return None
    return sum(_program.counters("telemetry.cache_miss_total{fn=engine.")
               .values())
