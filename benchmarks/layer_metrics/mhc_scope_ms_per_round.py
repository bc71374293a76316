"""Models (``models/mhc.py`` under ``models/xing4.py``'s scope ``mhc``): device
time on the first chip, per round, of the four-row residual path in the
round program: the maps' scores and Sinkhorn iterations, the stream's
reads, writes and mixes, every pass, in ms (``_scopes.py``, by part): what
the program wrote under that name, beside ``mhc_ms_per_round``, which tells
the stream's operations by shape."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "mhc")
