"""Kernels (``ops/kda.py``): the chunked delta rule's share of its roofline
over the window's training steps, in per cent.  The least time the chip
could take is the larger of ``rule_flops`` over the published bf16 peak and
``rule_bytes`` over the published HBM bandwidth
(``benchmarks/flops/ling3.py``: forward and backward of every sequence the
traced rounds trained on; the products of the chunked algorithm, the bytes
of q, k, v, the decays, the steps and the output each once), over the
device time under the scope ``kda.rule`` in the round program
(``kda_rule_ms_per_round``).

**The bound is the bytes'** at the published sizes: a training step needs
6.9 GB through six layers (8.4 ms at 819 GB/s) against 0.66 TFLOP (3.3 ms
at 197 TFLOP/s).  What keeps the share low: the rule is ``jax.numpy``, so
XLA writes every chunk's decays, panels, pair matrices and solve to HBM
and reads them back (a kernel would keep them on the chip), the loop over
the chunks is 128 small steps a pass, and what a rematerialised layer makes
again is not work done."""

from benchmarks.harness.peaks import peak
from benchmarks.layer_metrics import _scopes


def read(r):
    spent_ms = _scopes.under_ms(r, "kda.rule")
    if not spent_ms:
        return None
    model, dataset = r.config["experiment"]["model"], r.config["dataset"]
    flops = r.bench.module("flops", r.config["family"])
    sequences = r.samples_per_round / r.chips          # a round, a chip
    least = max(
        flops.rule_flops(model, dataset, train=True)
        / peak(r.device_kind, "bf16_flops_per_s"),
        flops.rule_bytes(model, dataset, train=True)
        / peak(r.device_kind, "hbm_bytes_per_s"))
    return 100.0 * sequences * least / (spent_ms * 1e-3)
