"""Models (``models/moe.py`` ``ExpertShare``, the scope ``moe``): device time
on the first chip, per round, of a whole expert layer in the round
program, forward, backward and rematerialised: the router, the pairs'
table and sort, the tile loop with a tile's rows and the banks' sums, the
latent maps where there are any, the shared expert, in ms (``_scopes.py``,
by part).  Its parts: ``moe_route_ms_per_round``, ``moe_tiles_ms_per_round``;
the rest is ``moe.pairs``, ``moe.shared`` and the maps."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.under_ms(r, "moe")
