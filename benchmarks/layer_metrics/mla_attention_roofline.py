"""Kernels (``ops/attention.py`` under ``models/mla.py``): the attention
kernel's share of its roofline over the window's training steps, in per
cent.  The least time the chip could take is the larger of
``attention_flops`` over the published bf16 peak and ``attention_bytes``
over the published HBM bandwidth (``benchmarks/flops/xing4.py``: forward
and backward of every sequence the traced rounds trained on, causal pairs
of 32 heads at 192 for the scores and 128 for the values, in the five
layers and a prediction module's where one is built), over the device time of the kernel's
custom calls in the round program (``_eva.py`` says how the trace names
them): ``gqa_attention_roofline``'s reader, which takes the counts from the
configuration's own family, imported and not copied.

**The bound is the operations'**: at 8,192 positions the kernel needs
10.3 TFLOP a training step (52.3 ms at 197 TFLOP/s) against 5.0 GB (6.2
ms at 819 GB/s).  What keeps the share under 100%: the backward recomputes
the scores (not work done), the diagonal blocks are half masked, and the
192-wide scores fill one and a half of the chip's 128-lane tiles (the
padded lanes are not counted)."""

from benchmarks.layer_metrics.gqa_attention_roofline import read  # noqa: F401
