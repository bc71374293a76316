"""Round program (``fed/programs.py``, the scope ``aggregate``): device time
on the first chip, per round, of what turns the clients' results into one:
update norms, clipping and noise, masks, the weighted or robust sum and, on
a mesh, the ``psum``s, in ms (``_scopes.py``, the by-phase cut)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "aggregate")
