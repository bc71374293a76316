"""Kernels (``ops/attention.py`` under ``ops/eva.py``): device time of the
attention kernel's three custom calls (forward, dQ, dK/dV) on the first
chip in the round program, per round, in ms (``_eva.py`` says how the
trace names them).  With rematerialised blocks the forward call runs
twice a step."""

from benchmarks.layer_metrics import _eva


def read(r):
    spent = _eva.training_kernel_seconds(r)
    if spent is None or not r.rounds:
        return None
    return spent * 1e3 / r.rounds
