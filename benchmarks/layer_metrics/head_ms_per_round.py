"""Models (the scope ``head`` in ``models/evabyte.py``, ``nemotron_h.py``,
``xing4.py`` and ``fed/losses.py``): device time on the first chip, per
round, of a language model's last norm, its logits and the per-token loss
in the round program, every pass, in ms (``_scopes.py``, by part)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "head")
