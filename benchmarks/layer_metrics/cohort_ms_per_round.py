"""Round program (``fed/programs.py``, the scope ``cohort``): device time
on the first chip, per round, of the cohort's draw (``draw_cohort``,
``rank_cohort``), the gather of its rows out of the resident clients, its
keys and step budgets, in ms (``_scopes.py``, the by-phase cut)."""

from benchmarks.layer_metrics import _scopes


def read(r):
    return _scopes.bucket_ms(r, "cohort")
