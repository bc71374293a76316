"""Models (``models/moe.py`` ``LatentMoEShare``): the static rows of one
layer's grouped products a step, from the gauge ``moe.dispatch_rows``:
tokens times the most held experts a token can choose (16,384 x 8 in
``nemotron_hybrid_seq16k``; the rows routed are 0.34 a token, and the
products' work follows those).  A program without the share layer never
sets it, and the line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("moe.dispatch_rows")
