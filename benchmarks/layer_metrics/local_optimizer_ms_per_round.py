"""Round program (``fed/local.py``, the scope ``local.optimizer`` inside
``local``): device time on the first chip, per round, of the clients'
optimiser state: its initial value, each local step's update of state and
parameters, the masking of steps past a budget, the parameters' change at
the end, in ms (``_scopes.py``, the by-phase cut).  Float32 state per
vmapped client lives here."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "local.optimizer")
