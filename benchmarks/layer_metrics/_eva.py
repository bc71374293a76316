"""The attention kernel of ``ops/attention.py`` on the device trace, for
the ``eva_attention_*`` readers beside this file.

How the trace names it, seen on a v5e trace of the cell (my chip runs, PR
28; PERF.md section 5): the three ``pallas_call``s carry the names
``flash_fwd``, ``flash_dq`` and ``flash_dkv``; XLA names each custom call
after it (``flash_fwd.72``, ``flash_dq.36``, ``flash_dkv.39`` in the round
program; under other transformations with more around it, as in
``transpose_jvp_flash_dkv__.1``), and ``harness/xplane.py`` keeps that name
in front of the largest array the call touches (``flash_dkv.39
bf16[256,3072,128]``: 256 folded rows of heads, 896 + 2,048 keys padded to
a block multiple).  A program whose attention
writes its scores out has no such operation, and every reader gives None.
"""

from __future__ import annotations

import bisect
from typing import Optional

from benchmarks.harness import xplane

KERNEL_NAMES = ("flash_fwd", "flash_dq", "flash_dkv")


def is_kernel(label: str) -> bool:
    name = label.split(" ", 1)[0]
    return any(k in name for k in KERNEL_NAMES)


def training_kernel_seconds(r) -> Optional[float]:
    """Self time on the first chip, inside the traced window, of the
    kernel's custom calls that ran in an execution of a round program: the
    evaluation's forward calls (told by the module's name, as
    ``round_device_ms`` tells them) are left out.  None where the trace
    has no such call."""
    if r.trace is None or not r.trace.devices:
        return None
    device = r.trace.devices[min(r.trace.devices)]
    window = r.trace.window_ns
    modules = sorted(xplane.clip(device.modules, window), key=lambda m: m[1])
    starts = [m[1] for m in modules]
    kept = []
    for event in xplane.clip(device.ops, window):
        i = bisect.bisect_right(starts, event[1])
        if i and event[1] < modules[i - 1][1] + modules[i - 1][2] and (
                "eval" in xplane.module_name(modules[i - 1][0])):
            continue
        kept.append(event)
    spent = sum(t for label, t in xplane.self_times(kept).items()
                if is_kernel(label))
    return spent or None
