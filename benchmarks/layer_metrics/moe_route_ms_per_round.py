"""Models (``models/moe.py`` ``ExpertShare.route``, the scope ``moe.route``):
device time on the first chip, per round, of the router in the round
program: the scores' product, ``lax.top_k``, the chosen scores and their
weights, every pass, in ms (``_scopes.py``, by part)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "moe.route")
