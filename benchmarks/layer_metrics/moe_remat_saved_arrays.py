"""Models (``models/moe.py`` ``ExpertShare`` under ``nn.remat``): the share
layer's names whose arrays a rematerialised layer keeps for its backward
instead of computing them again (the router's product, the choice and the
chosen scores, the pairs' rows and their pull-back, the routed rows), from
the gauge ``moe.remat_saved_arrays``, set at trace time on every build of
the two models that hold a share layer (0: ``remat`` is off and everything
is kept).  A program without the gauge never sets it, and the line leaves
the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("moe.remat_saved_arrays")
