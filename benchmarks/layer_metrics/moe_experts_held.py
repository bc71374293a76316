"""Models (``models/moe.py`` ``LatentMoEShare``): the routed experts this
chip holds of a layer's mixture, from the gauge ``moe.experts_held`` set
at trace time where the share layer is built (8 of the published 512 in
``nemotron_hybrid_seq16k``).  A program without the share layer never sets
it, and the line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("moe.experts_held")
