"""Kernels (``ops/attention.py`` under ``models/mla.py``, scores over 192
and values of 128): device time of the attention kernel's three custom
calls (forward, dQ, dK/dV) on the first chip in the round program, per
round, in ms.  The trace names them as ``_eva.py`` says (``flash_fwd``,
``flash_dq``, ``flash_dkv``, each in front of the largest array the call
touches: here ``bf16[32,8192,192]``, 32 heads of one sequence), and what
is read of them is what ``gqa_attention_ms_per_round`` reads: its reader is
imported, not copied.  A rematerialised layer keeps the kernel's output
and log-sum, so the forward call runs once a step and layer."""

from benchmarks.layer_metrics.gqa_attention_ms_per_round import read  # noqa: F401
