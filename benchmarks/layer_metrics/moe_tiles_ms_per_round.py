"""Models (``models/moe.py`` ``routed_rows``, the scope ``moe.tiles``): device
time on the first chip, per round, of the loop over row tiles in the round
program: a tile's gathers, the grouped products (``ragged-dot``), the
scatter-adds and the banks' float32 sums, the forward and its
``custom_vjp`` backward alike, in ms (``_scopes.py``, by part)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "moe.tiles")
