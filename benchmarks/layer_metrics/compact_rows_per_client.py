"""Round program (``fed/local.py``'s working set of rows): rows of the
token-embedding table that one client's local steps train, from the gauge
``local.compact_rows`` set where the trainer first takes a working set (the
table's own rows are in ``local.compact_rows_of``).  A program that trains
the whole table never sets it, and the line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("local.compact_rows")
