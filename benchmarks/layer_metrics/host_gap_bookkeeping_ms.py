"""Orchestration (``fed/engine.py`` ``fit``): the part of
``host_gap_ms_per_round`` under the program's ``bookkeeping`` spans
(``memory_stats()``, record fields, counters, the lifecycle calls) and its
``log`` span (the caller's ``log_fn``), in ms a round."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.gap_ms_under(r, "bookkeeping", "log")
