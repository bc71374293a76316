"""Models (``models/``): model FLOP/s utilisation, in per cent.  The
operations forward and backward need per example, from shapes by
``benchmarks/flops/<family>.py``, times the examples per second the
window completed, over chips times the published bf16 peak of the
device."""

from benchmarks.harness.peaks import peak


def read(r):
    if not r.rounds or r.window_s <= 0:
        return None
    flops = r.bench.module("flops", r.config["family"]).train_flops(
        r.config["experiment"]["model"], r.config["dataset"])
    per_s = flops * r.rounds * r.samples_per_round / r.window_s
    return 100.0 * per_s / (r.chips * peak(r.device_kind, "bf16_flops_per_s"))
