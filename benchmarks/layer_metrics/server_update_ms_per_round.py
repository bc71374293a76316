"""Round program (``fed/programs.py`` ``finish_round``, the scope
``server``): device time on the first chip, per round, of the mean update,
``fed/strategies.py``'s server step and the round's metrics, in ms
(``_scopes.py``, the by-phase cut)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "server")
