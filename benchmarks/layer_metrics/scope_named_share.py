"""Device: of the round program's self time on the first chip, the share
of the operations that lie under a name the program wrote (a ``Scope``
whose path is not empty, ``_scopes.py``), in per cent.  The rest is what
XLA made itself, with no ``op_name`` or one without a path (copies, a
``reduce_sum``), outside every named loop."""

from benchmarks.layer_metrics import _scopes


def read(r):
    named = _scopes.ms(r, lambda scope: bool(scope.path))
    whole = _scopes.ms(r, lambda scope: True)
    return None if not whole else 100.0 * named / whole
