"""Kernels (``ops/ssd.py`` under ``models/nemotron_h.py``'s scope ``ssd``):
device time on the first chip, per round, of the state-space scan in the
round program, every pass, in ms (``_scopes.py``, by part): what the program
wrote under that name, beside ``ssd_ms_per_round``, which tells the scan's
operations by their chunked shapes."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "ssd")
