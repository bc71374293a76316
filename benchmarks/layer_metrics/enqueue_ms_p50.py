"""Orchestration (``fed/engine.py``): median ``phase_update_s`` of the
window's round records, in ms.  The span closes when the round program is
enqueued, not when it has run, so this is what the host spends getting a
round onto the device."""

import statistics


def read(r):
    spans = [rec["phase_update_s"] for rec in r.records
             if "phase_update_s" in rec]
    return statistics.median(spans) * 1e3 if spans else None
