"""Models (``models/mhc.py``): the rows of a token's residual stream, from
the gauge ``mhc.streams`` set at trace time where the stream maps are built
(4 in ``xing_mla_mhc_seq8k``, the published ``hc_mult``).  A program on
the plain residual path never sets it, and the line leaves the metric
out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("mhc.streams")
