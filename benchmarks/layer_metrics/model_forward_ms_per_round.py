"""Models: device time on the first chip, per round, of the model's
forward pass in the clients' local steps, loss included: the round
program's operations under ``jvp(`` and outside ``local.optimizer``, in ms
(``_scopes.py``, the by-phase cut).  What a rematerialised block repeats in
the backward pass is ``model_remat_ms_per_round``'s."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "forward")
