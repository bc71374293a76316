"""Device: of the first chip's self time inside the window, in executions
of the round and the evaluation program, the share whose instruction name
is in the scope table of the program it ran in (``_scopes.py``), in per
cent.  100 says the tables are of the executables that ran; under
``_scopes.MATCHED_FLOOR`` every by-scope reader gives None."""

from benchmarks.layer_metrics import _scopes


def read(r):
    parts = _scopes.split(r)
    return None if parts is None else parts.matched_share
