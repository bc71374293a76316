"""Orchestration: the share of the window ``fit()`` spent in its holdout
evaluations (sum of ``phase_eval_s`` over the window), in per cent."""


def read(r):
    if not r.records or r.window_s <= 0:
        return None
    spent = sum(rec.get("phase_eval_s", 0.0) for rec in r.records)
    return 100.0 * spent / r.window_s
