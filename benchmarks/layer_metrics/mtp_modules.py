"""Models (``models/xing4.py``): the sequential multi-token-prediction
modules the model was built with, from the gauge ``mtp.modules`` set at
trace time on every build.  **0 in ``xing_mla_mhc_seq8k``**: the cell's
configuration leaves the module out (its ``departures`` say why: the
parity probe has no room for it), the logits have one head a position and
the holdout 32,768 labels; 1 where a configuration builds it.  A program
of another family never sets it, and the line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("mtp.modules")
