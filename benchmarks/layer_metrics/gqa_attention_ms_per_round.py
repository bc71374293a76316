"""Kernels (``ops/attention.py`` under ``models/attention.py``, fewer
key/value than query heads): device time of the attention kernel's three
custom calls (forward, dQ, dK/dV) on the first chip in the round program,
per round, in ms.  The trace names them as ``_eva.py`` says
(``flash_fwd``, ``flash_dq``, ``flash_dkv``, each in front of the largest
array the call touches: here ``bf16[8,16384,128]``, 8 query heads of one
sequence, the shared key/value head copied to each); its helper is
imported, not copied.  A rematerialised layer keeps the kernel's output
and log-sum, so the forward call runs once a step."""

from benchmarks.layer_metrics import _eva


def read(r):
    spent = _eva.training_kernel_seconds(r)
    if spent is None or not r.rounds:
        return None
    return spent * 1e3 / r.rounds
