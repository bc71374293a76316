"""Models (``models/mhc.py``): device time of the widened residual path on
the first chip in the round program, per round, in ms: the maps' scores,
the Sinkhorn iterations, the reads of the four-row stream into a
sublayer's input and the writes back into all its rows, forward and
backward, in every sublayer.

The path is no kernel with a name of its own: XLA compiles it into fusions,
and ``harness/xplane.py`` keeps each operation's name in front of the
largest array it touches.  So its operations are told by the shapes only
they have, from the configuration's sizes (a client axis of 1 and other
dimensions of 1 are dropped first, as ``_hybrid.py`` drops them; its
helpers are imported, not copied), with ``n = hc_streams``, ``L`` the
positions, ``C`` the width and ``m = 2 n + n^2`` scores a token:

the stream
    ``[n, L, C]`` (``bf16[4,8192,3584]``, and in float32 where a fusion
    writes a gradient): the reads ``sum_j H_pre[j] X[j]``, the writes
    ``sum_j H_res[i, j] X[j] + H_post[i] F``, their transposes in the
    backward pass and the sum of the rows in front of a head.  XLA fuses
    into them what stands next to them and has no larger array: the
    RMSNorm's sum of squares over the stream, the cast of a sublayer's
    output, the broadcast of the embedding into the rows.
the maps' scores
    the products ``X (w . Phi)`` and their transposes: a dimension of
    ``m`` (24) beside ``L`` or beside the stream's flattened or split
    width (``[8192,24]``, ``[24,8192]``, ``[14336,24]``, ``[4,3584,24]``).
the maps
    the positions last and only rows in front of them: ``[n, L]``, ``[n,
    n, L]``, ``[n^2, L]``: the sigmoids, the exponential and the
    iterations' forty normalisations with what autodiff keeps of them.

Not among them: a sublayer's input ``[L, C]`` once it is read (the norm,
attention and the feed-forward own it).  A program without such operations
(another family; the parent of the PR that added this reader) gives
None."""

import math

from benchmarks.layer_metrics import _hybrid


def stream_ops(model: dict, dataset: dict):
    """Tells the residual path's operations by their label."""
    n, width = model["hc_streams"], model["width"]
    length = dataset["input_shape"][0]
    scores = 2 * n + n * n
    rows = {n, n * n, 2 * n, scores}

    def mine(label: str) -> bool:
        dims = _hybrid.dims_of(label)
        if len(dims) < 2:
            return False
        if (dims[-1] == width and n in dims and length in dims
                and math.prod(dims) == n * length * width):
            return True
        if scores in dims and (length in dims or n * width in dims
                               or width in dims):
            return True
        return dims[-1] == length and math.prod(dims[:-1]) in rows and all(
            d in rows for d in dims[:-1])

    return mine


def read(r):
    model, dataset = r.config["experiment"]["model"], r.config["dataset"]
    if "hc_streams" not in model or not r.rounds:
        return None
    spent = _hybrid.training_seconds(r, stream_ops(model, dataset))
    return None if spent is None else spent * 1e3 / r.rounds
