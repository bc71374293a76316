"""Models: device time on the first chip, per round, of the model's
backward pass in the clients' local steps: the round program's operations
under ``transpose(jvp(`` and outside ``local.optimizer``, but for what is
computed again there (``model_remat_ms_per_round``), in ms (``_scopes.py``,
the by-phase cut)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "backward")
