"""Orchestration (``fed/engine.py`` ``run_round``): the part of
``host_gap_ms_per_round`` under the program's ``enqueue`` span, from the
call of the round program to its first operation on the device, in ms a
round.  ``enqueue_ms_p50`` is the whole span, idle device or not."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.gap_ms_under(r, "enqueue")
