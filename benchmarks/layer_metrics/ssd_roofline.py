"""Kernels (``ops/ssd.py``): the state-space scan's share of its roofline
over the window's training steps, in per cent.  The least time the chip
could take is the larger of ``scan_flops`` over the published bf16 peak and
``scan_bytes`` over the published HBM bandwidth
(``benchmarks/flops/nemotron_h.py``: forward and backward of every
sequence the traced rounds trained on; the operations of the chunked
algorithm, the bytes of ``x``, ``B``, ``C``, the steps and ``y`` each
once), over the device time of the scan's operations in the round program
(``_hybrid.py`` says how the trace names them).

**The bound is the bytes'**: a training step needs 2.0 GB (2.4 ms at 819
GB/s) against 0.33 TFLOP (1.7 ms at 197 TFLOP/s).  What keeps the share
low: XLA writes every chunk's decay and score matrices and the chunks'
states to HBM and reads them back (a kernel would keep them on the chip),
and rematerialised layers run the forward scan twice (not work done).
"""

from benchmarks.harness.peaks import peak
from benchmarks.layer_metrics import _hybrid


def read(r):
    spent = _hybrid.scan_seconds(r)
    if spent is None:
        return None
    model, dataset = r.config["experiment"]["model"], r.config["dataset"]
    flops = r.bench.module("flops", r.config["family"])
    sequences = r.rounds * r.samples_per_round / r.chips
    least = max(
        flops.scan_flops(model, dataset, train=True)
        / peak(r.device_kind, "bf16_flops_per_s"),
        flops.scan_bytes(model, dataset, train=True)
        / peak(r.device_kind, "hbm_bytes_per_s"))
    return 100.0 * sequences * least / spent
