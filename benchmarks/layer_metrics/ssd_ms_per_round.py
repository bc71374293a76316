"""Kernels (``ops/ssd.py``, no kernel of its own yet: XLA's fusions):
device time of Mamba-2's chunked state-space scan on the first chip in the
round program, per round, in ms: the decay and score matrices of every
chunk, the two products inside a chunk, the chunks' states, the scan over
the chunks and the read of the carried state, forward and backward, in
every ``M`` layer; not the projections, the convolution or the gated norm
around it.  ``_hybrid.py`` says how the trace names them: by the chunked
shapes only the scan has."""

from benchmarks.layer_metrics import _hybrid


def read(r):
    spent = _hybrid.scan_seconds(r)
    return None if spent is None else spent * 1e3 / r.rounds
