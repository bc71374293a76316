"""Models (``models/evabyte.py``): named arrays a rematerialised block
keeps for its backward instead of computing them again (the attention
kernel's output and its log-sum: 2), from the gauge
``evabyte.remat_saved_arrays``, set at trace time on every build of the
model (0: ``remat`` is off and everything is kept).  A program without the
gauge never sets it, and the line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("evabyte.remat_saved_arrays")
