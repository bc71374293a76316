"""Kernels (``ops/attention.py`` under ``ops/eva.py``): the attention
kernel's share of its roofline over the window's training steps, in per
cent.  The least time the chip could take is the larger of
``attention_flops`` over the published bf16 peak and ``attention_bytes``
over the published HBM bandwidth (``benchmarks/flops/evabyte.py``, forward
and backward of every sequence the traced rounds trained on, counted from
the mask and the shapes), over the device time of the kernel's custom
calls in the round program (``_eva.py``).

**The bound is the operations'**: at 16,384 positions the kernel needs
4.74 TFLOP a training step (24.1 ms at 197 TFLOP/s) against 7.9 GB (9.6 ms
at 819 GB/s).  What keeps the share under 100%: the forward call runs
twice under rematerialisation and the backward recomputes the scores
(neither is work done), the diagonal blocks are half masked, and the
summaries of later windows are visited and masked, not skipped.
"""

from benchmarks.harness.peaks import peak
from benchmarks.layer_metrics import _eva


def read(r):
    spent = _eva.training_kernel_seconds(r)
    if spent is None or not r.rounds:
        return None
    flops = r.bench.module("flops", r.config["family"])
    model, dataset = r.config["experiment"]["model"], r.config["dataset"]
    sequences = r.rounds * r.samples_per_round / r.chips
    least = max(
        flops.attention_flops(model, dataset, train=True)
        / peak(r.device_kind, "bf16_flops_per_s"),
        flops.attention_bytes(model, dataset, train=True)
        / peak(r.device_kind, "hbm_bytes_per_s"))
    return 100.0 * sequences * least / spent
