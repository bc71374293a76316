"""Models: device time on the first chip, per round, of what
rematerialisation repeats: the round program's operations under
``rematted_computation``, the forward pass of a ``jax.checkpoint``ed block
made again for its backward pass, in ms (``_scopes.py``, the by-phase cut).
None for a model without a rematerialised block."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "remat") or None
