"""Kernels (``ops/attention.py`` under ``models/attention.py``): the
attention kernel's share of its roofline over the window's training
steps, in per cent.  The least time the chip could take is the larger of
``attention_flops`` over the published bf16 peak and ``attention_bytes``
over the published HBM bandwidth (``benchmarks/flops/nemotron_h.py``:
forward and backward of every sequence the traced rounds trained on,
causal pairs of every query head, keys and values counted at the heads
they have), over the device time of the kernel's custom calls in the round
program (``_eva.py`` says how the trace names them).

**The bound is the operations'**: at 16,384 positions and 8 query heads
the kernel needs 1.65 TFLOP a training step (8.4 ms at 197 TFLOP/s)
against 0.23 GB (0.3 ms at 819 GB/s).  What keeps the share under 100%:
the backward recomputes the scores (not work done), the diagonal blocks
are half masked, and the key/value head is read once a query head.
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
