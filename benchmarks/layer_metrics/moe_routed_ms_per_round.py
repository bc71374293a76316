"""Models (``models/moe.py`` ``LatentMoEShare``): device time of the
routed experts on the first chip in the round program, per round, in ms:
the router's scores and the choice of 22 of 512, the sort of the (token,
choice) pairs, the gathers into rows at their static bound, the grouped
products (XLA's ``ragged-dot`` kernel, whose work follows the rows routed),
the squared relu between them and the weighted scatter-add back, forward
and backward, in every ``E`` layer; not the latent projections, not the
shared expert.  ``_hybrid.py`` says how the trace names them: by the
shapes only this part has, and ``ragged-dot`` by its name."""

from benchmarks.layer_metrics import _hybrid


def read(r):
    spent = _hybrid.routed_seconds(r)
    return None if spent is None else spent * 1e3 / r.rounds
