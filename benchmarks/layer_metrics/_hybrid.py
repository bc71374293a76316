"""The hybrid stack's own parts (``models/nemotron_h.py``) on the device
trace, for the ``ssd_*`` and ``moe_routed_*`` readers beside this file.

Neither part is a kernel with a name of its own: XLA compiles both into
fusions (``fusion.4298``), sorts and copies, and ``harness/xplane.py``
keeps each operation's name in front of the largest array it touches.  So a
part is told by the shapes only it has, built here from the
configuration's sizes, as a v5e trace of the cell showed them (my chip
runs, PR 33; a client axis of 1 and other dimensions of 1 stand anywhere in
them and are dropped first):

the scan of ``ops/ssd.py``
    arrays cut into chunks: the first dimension is the number of chunks
    (128) and the array has the elements of the carried states
    ``f32[128,2,16,64,128]`` (chunks, groups, heads a group, head width,
    state), of the inputs by chunk ``[128,128,2,16,64]``, of the decay and
    score matrices ``[128,2,16,128,128]``, of ``C B^T`` ``[128,2,128,128]``,
    of ``B`` or ``C`` by chunk ``[128,128,2,128]`` or of the steps
    ``[128,128,2,16]``, in four dimensions or more.  The projections, the
    convolution and the gated norm around it are not the scan.
the routed experts of ``models/moe.py`` ``LatentMoEShare``
    the router's scores and choice (a last dimension of 512 experts, or of
    22 choices), the sort of a block's (token, choice) pairs (``[90112]``:
    4,096 tokens x 22; ``[360448]`` for the whole sequence), the rows at
    their static bound (``[32768,1024]``, ``[32768,2688]``, ``[32768]``:
    4,096 tokens x 8 held experts), the experts' banks (``[8,1024,2688]``,
    ``[8,2688,1024]``, their gradients and updates with them) and XLA's
    grouped-product kernel, which has a name: ``ragged-dot``.  The latent
    projections and the shared expert are not among them.

A program without such operations (another family; the parent of the PR
that added these readers) gives None everywhere.
"""

from __future__ import annotations

import bisect
import math
import re
from typing import Callable, Optional

from benchmarks.harness import xplane

DIMS = re.compile(r"\[([\d,]*)\]$")
# Tokens that ``LatentMoEShare`` routes at a time (its ``token_block``).
TOKEN_BLOCK = 4096


def dims_of(label: str) -> tuple[int, ...]:
    """The dimensions of the largest array in an operation's label, those
    of 1 dropped."""
    found = DIMS.search(label)
    if not found or not found.group(1):
        return ()
    return tuple(d for d in map(int, found.group(1).split(",")) if d != 1)


def scan_ops(model: dict, dataset: dict) -> Callable[[str], bool]:
    """Tells the operations of the state-space scan by their label."""
    length, chunk = dataset["input_shape"][0], model["chunk_size"]
    chunks, chunk = -(-length // chunk), min(chunk, length)
    groups, state = model["mamba_groups"], model["ssm_state_size"]
    per_group = model["mamba_heads"] // groups
    width = model["mamba_head_dim"]
    sizes = {chunks * groups * n for n in (
        per_group * width * state, chunk * per_group * width,
        per_group * chunk * chunk, chunk * chunk, chunk * state,
        chunk * per_group)}

    def mine(label: str) -> bool:
        dims = dims_of(label)
        return (len(dims) >= 4 and dims[0] == chunks
                and math.prod(dims) in sizes)

    return mine


def routed_ops(model: dict, dataset: dict) -> Callable[[str], bool]:
    """Tells the operations of the routed experts by their label."""
    length = dataset["input_shape"][0]
    block = min(TOKEN_BLOCK, length)
    choices, held = model["experts_per_token"], model["experts_held"]
    rows = block * min(choices, held)
    pairs = {block * choices, length * choices}
    banks = {(held, model["latent_dim"], model["expert_dim"]),
             (held, model["expert_dim"], model["latent_dim"])}

    def mine(label: str) -> bool:
        if label.startswith("ragged-dot"):
            return True
        dims = dims_of(label)
        if not dims:
            return False
        return (dims[-1] == model["num_experts"]
                or (len(dims) >= 2 and choices in dims[1:])
                or bool(pairs & set(dims))
                or dims[0] == rows
                or dims in banks)

    return mine


def training_seconds(r, mine: Callable[[str], bool]) -> Optional[float]:
    """Self time on the first chip, inside the traced window, of the
    operations ``mine`` picks by their label that ran in an execution of a
    round program: the evaluation's (told by the module's name, as
    ``round_device_ms`` tells them) are left out.  None where the trace has
    no such operation."""
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
                if mine(label))
    return spent or None


def part_seconds(r, ops: Callable[[dict, dict], Callable[[str], bool]],
                 key: str) -> Optional[float]:
    """``training_seconds`` of the part ``ops`` (``scan_ops`` or
    ``routed_ops``) tells, for a configuration whose model has ``key``; None
    for any other, and where nothing was traced or no round completed."""
    model, dataset = r.config["experiment"]["model"], r.config["dataset"]
    if key not in model or not r.rounds:
        return None
    return training_seconds(r, ops(model, dataset))


def scan_seconds(r) -> Optional[float]:
    return part_seconds(r, scan_ops, "ssm_state_size")


def routed_seconds(r) -> Optional[float]:
    return part_seconds(r, routed_ops, "experts_held")
