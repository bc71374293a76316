"""Round program (``fed/local.py``, under ``local``, in neither the model's
passes nor ``local.optimizer``): device time on the first chip, per round,
of what the scan of local steps does around them: a batch's draw and
gather, the working set of rows, the loop's own carries and copies, and
what XLA made in that loop without a name of its own (a ``sort``, a
``copy``), in ms (``_scopes.py``, the by-phase cut)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "local.other")
