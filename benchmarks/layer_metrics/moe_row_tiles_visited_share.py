"""Models (``models/moe.py`` ``LatentMoEShare``): the row tiles the routed
experts visited in the traced rounds' round programs, of the tiles their
rows' static bound holds, in per cent.  The share layer visits its rows in
tiles of ``moe.row_tile`` by a loop whose trip count follows the routing;
100% is every tile of ``moe.dispatch_rows`` (a routing that sends every
token to every held expert, or a program that works at the bound).

A visit is told by XLA's grouped-product kernel (``ragged-dot``; its
``ragged-dot-metadata`` companion is shared between calls and not counted):
events on the first chip inside executions of a round program, counted, not
timed.  A visited tile runs the kernel twice in a forward pass and six
times in the backward pass (its two products again, two for the rows, two
for the banks), and a rematerialised layer (the model's ``remat``) runs the
forward twice a step.  The executions there would be if every tile were
visited: tiles a layer and step (``moe.dispatch_rows`` over
``moe.row_tile``) x the calls above x the layers (``hybrid.layers{kind=moe}``)
x the steps the chip's clients took in the traced rounds.

A program without ``moe.row_tile`` (another family; the parent of the PR
that added this reader) gives None."""

import bisect
from typing import Optional

from benchmarks.harness import xplane
from benchmarks.layer_metrics import _program

FORWARD_CALLS, BACKWARD_CALLS = 2, 6


def is_visit(label: str) -> bool:
    return (label.startswith("ragged-dot")
            and not label.startswith("ragged-dot-metadata"))


def visits_in_round_programs(trace: xplane.Trace) -> int:
    """Grouped-product events on the first chip that began inside the
    traced window in an execution of a program other than the evaluation's
    (told by the module's name, as ``round_device_ms`` tells them)."""
    device = trace.devices[min(trace.devices)]
    lo, hi = trace.window_ns
    modules = sorted(device.modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    count = 0
    for label, start, _ in device.ops:
        if not (lo <= start < hi and is_visit(label)):
            continue
        i = bisect.bisect_right(starts, start)
        if i and start < modules[i - 1][1] + modules[i - 1][2] and (
                "eval" not in xplane.module_name(modules[i - 1][0])):
            count += 1
    return count


def calls_at_the_bound(rows: float, tile: float, layers: float, steps: float,
                       remat: bool) -> float:
    tiles = -(-rows // tile)
    calls = (2 if remat else 1) * FORWARD_CALLS + BACKWARD_CALLS
    return tiles * calls * layers * steps


def read(r) -> Optional[float]:
    tile = _program.counter("moe.row_tile")
    rows = _program.counter("moe.dispatch_rows")
    layers = _program.counter("hybrid.layers{kind=moe}")
    if (not tile or not rows or not layers or not r.rounds
            or r.trace is None or not r.trace.devices):
        return None
    fed, model = r.config["experiment"]["fed"], r.config["experiment"]["model"]
    batch = r.traffic.get("batch", fed["batch_size"])
    steps = r.rounds * r.samples_per_round / r.chips / batch
    visits = visits_in_round_programs(r.trace)
    if not visits:
        return None
    return 100.0 * visits / calls_at_the_bound(
        rows, tile, layers, steps, bool(model.get("remat")))
