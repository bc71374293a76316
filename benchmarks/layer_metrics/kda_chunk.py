"""Kernels (``ops/kda.py`` under ``models/ling3.py``): the positions a chunk
of the delta rule holds, from the gauge ``kda.chunk`` (64 in
``ling_kda_mla_hybrid``): the pairs' products and the triangular solve grow
with its square, the loop over the chunks shrinks with it.  A program that
never set it (one without the mixer) reads None, and the line leaves the
metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("kda.chunk")
