"""Models (``models/cnn.py``): stages of the CNN computed on the view that
folds two adjacent columns into the channel axis, so that a 64-channel
activation fills the 128 lanes of a tile; from the gauge
``cnn.lane_folded_stages``, set at trace time on every build of the model
(0: every stage took the plain path).  A program without the gauge never
sets it, and the line leaves the metric out."""

from benchmarks.layer_metrics import _program


def read(r):
    return _program.counter("cnn.lane_folded_stages")
