"""Kernels (``ops/eva.py``): the most keys a query's softmax runs over on
the kernel path (its own window and one summary for every chunk of the
windows before it), from the gauge ``eva.keys_per_query_max`` set where
the flash path of ``eva_attention`` is built.  A program whose EVA
attention writes its scores out (``attn_impl: dense``) never sets it, and
the line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("eva.keys_per_query_max")
