"""Device time by the program's own scopes, for the ``scope_*`` and
``*_ms_per_round`` readers beside this file that say so.

The device trace names an operation by its instruction (``fusion.3500``);
the program says which instruction lies under which of its names:
``telemetry.program_scopes()`` gives, for each compiled program that ran
(``jit_round_fn`` or, on a mesh, ``jit_body``; ``jit_eval_fn``), ``{instruction
name: Scope(phase, path)}`` read from the executable's own text
(``telemetry/runtime.py``).  ``phase`` is ``forward``, ``backward``,
``remat`` or ``none``; ``path`` holds the names the program wrote
(``telemetry.device_scope``s, flax's module and method names), outermost
first: ``("local", "NemotronH", "layer_3", "moe", "mixer",
"mixer.routed_latent", "moe.tiles")``.  An instruction XLA made without a
path of its own (a ``ragged-dot`` custom call, a ``sort``, a ``copy``) is
filed under the loop whose body holds it, if that has one.

Here, on the first chip and inside the traced window, every operation is
placed in the execution (an ``XLA Modules`` event) it ran in, as
``_hybrid.training_seconds`` places them: the round and the evaluation
program share instruction names, so a name is looked up in the table of
the program whose execution holds the operation.  Self times
(``xplane.self_times``: a ``while`` less its children) are summed by
``Scope``.  Two cuts of the round program's time are read from that:

by phase (``bucket``), each operation in exactly one:
    no name at all (``unnamed``: what XLA made itself, in no named loop);
    under ``local.optimizer``; else by its phase ``forward``, ``backward``,
    ``remat`` (only the model is differentiated); else under ``local``
    (``local.other``: the batch's draw and gather, the loop's carries,
    what XLA made in the loop of local steps without a name of its own);
    else ``cohort``, ``aggregate``, ``server``; else ``other``.  Their sum
    is the round program's busy time; with the idle inside the program,
    ``round_device_ms``.
by part (``under``):
    everything under one name, across phases: ``moe``, ``ssd``, ``head``.

A fusion is filed under the one ``op_name`` XLA gave it, whatever it
fused: where XLA fuses across two scopes, one of them gets the whole.

Every reader gives None where the program offers no table (the parent of
the PR that added them), where nothing was traced on a device, or where
less than ``MATCHED_FLOOR`` of the time was found in the tables: a split
of names that do not fit the program that ran says nothing.  The tables
are built on first ask, after the window: a load of the executable or,
where the compile cache held one under older names, one build
(``scope_table_s``).
"""

from __future__ import annotations

import bisect
import dataclasses
import sys
from typing import Callable, Optional

from benchmarks.harness import xplane

MATCHED_FLOOR = 99.0       # per cent
ENGINE_SCOPES = ("cohort", "aggregate", "server")


@dataclasses.dataclass
class Split:
    matched_s: float        # round and evaluation programs, in a table
    total_s: float          # round and evaluation programs, all
    round: dict             # the round programs' seconds by Scope

    @property
    def matched_share(self) -> float:
        return 100.0 * self.matched_s / self.total_s

    @property
    def round_s(self) -> float:
        return sum(self.round.values())


def tables() -> Optional[dict]:
    """The program's scope tables by module name; None where it has none
    to offer."""
    from colearn_federated_learning_tpu import telemetry

    ask = getattr(telemetry, "program_scopes", None)
    if ask is None:
        return None
    try:
        return ask() or None
    except Exception as exc:  # noqa: BLE001 - a metric left out, and said
        print(f"scope tables: {type(exc).__name__}: {exc}", file=sys.stderr)
        return None


def by_program(trace: xplane.Trace) -> dict[str, list]:
    """The first chip's operations inside the window, by the name of the
    program in whose execution each began."""
    device = trace.devices[min(trace.devices)]
    window = trace.window_ns
    modules = sorted(xplane.clip(device.modules, window), key=lambda m: m[1])
    starts = [m[1] for m in modules]
    programs: dict[str, list] = {}
    for event in xplane.clip(device.ops, window):
        i = bisect.bisect_right(starts, event[1])
        if i and event[1] < modules[i - 1][1] + modules[i - 1][2]:
            programs.setdefault(
                xplane.module_name(modules[i - 1][0]), []).append(event)
    return programs


def split_trace(trace: xplane.Trace, scopes: dict) -> Optional[Split]:
    """The trace's time by ``Scope`` under the tables ``scopes``; None
    where no program with a table ran."""
    out = Split(0.0, 0.0, {})
    for program, events in by_program(trace).items():
        table = scopes.get(program)
        if table is None:
            continue
        for label, seconds in xplane.self_times(events).items():
            out.total_s += seconds
            scope = table.get(label.split(" ", 1)[0])
            if scope is None:
                continue
            out.matched_s += seconds
            if "eval" not in program:
                out.round[scope] = out.round.get(scope, 0.0) + seconds
    return out if out.total_s else None


def split(r) -> Optional[Split]:
    """``split_trace`` of the reading's trace under the program's tables,
    made once a reading."""
    if "_scope_split" not in vars(r):
        scopes = None
        if r.trace is not None and r.trace.devices:
            scopes = tables()
        vars(r)["_scope_split"] = (
            split_trace(r.trace, scopes) if scopes else None)
    return vars(r)["_scope_split"]


def bucket(scope) -> str:
    """The one part of the by-phase cut an operation belongs to."""
    path = scope.path
    if not path:
        return "unnamed"
    if "local.optimizer" in path:
        return "local.optimizer"
    if scope.phase != "none":
        return scope.phase
    if "local" in path:
        return "local.other"
    return next((name for name in ENGINE_SCOPES if name in path), "other")


def ms(r, select: Callable) -> Optional[float]:
    """Self time of the round program's operations whose ``Scope``
    ``select`` picks, in ms a round (0 where it picks none: XLA may fuse a
    small part away into its neighbour); None where nothing can be said
    (module docstring)."""
    parts = split(r)
    if (parts is None or not r.rounds
            or parts.matched_share < MATCHED_FLOOR):
        return None
    spent = sum(t for scope, t in parts.round.items() if select(scope))
    return spent * 1e3 / r.rounds


def bucket_ms(r, name: str) -> Optional[float]:
    return ms(r, lambda scope: bucket(scope) == name)


def under_ms(r, name: str) -> Optional[float]:
    return ms(r, lambda scope: name in scope.path)
