"""Kernels (``ops/kda.py``, scope ``kda.rule``): device time on the first
chip, per round, of the chunked gated delta rule alone in the round program
(the running sums and decays, the pairs' products, the triangular solve,
the loop over the chunks and the outputs; forward, made again and
backward), in ms (``_scopes.py``, by part).  A program without the scope
reads None."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "kda.rule")
