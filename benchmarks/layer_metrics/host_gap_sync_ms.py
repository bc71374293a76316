"""Orchestration (``fed/engine.py`` ``run_round``): the part of
``host_gap_ms_per_round`` under the program's ``sync_metrics`` span, from
the device's last operation of a round to the host's having its metrics,
in ms a round."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.gap_ms_under(r, "sync_metrics")
