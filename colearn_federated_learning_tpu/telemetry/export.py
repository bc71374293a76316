"""Trace export: Chrome-trace/Perfetto JSON + human-readable summary.

The on-disk format is the Chrome Trace Event JSON object form —
``{"traceEvents": [...]}`` with complete (``"ph": "X"``) events — which
both ``chrome://tracing`` and https://ui.perfetto.dev open directly.
Span identity (trace/span/parent ids) rides in each event's ``args`` so
a loaded trace round-trips back into span dicts, and a ``metrics`` key
carries the :class:`~..telemetry.registry.MetricsRegistry` snapshot.

A ``--profile-dir`` run leaves a jax profile, whose device plane names
operations by instruction, and beside it ``program_scopes.json``
(:func:`write_program_scopes`, the program's scope tables):
:func:`summarize_profile` joins the two into device time by the program's
own scopes.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from typing import Optional

from colearn_federated_learning_tpu.telemetry.runtime import (
    Scope,
    program_scopes,
)
from colearn_federated_learning_tpu.telemetry.tracer import Span, Tracer

TRACE_VERSION = 1
SCOPES_FILE = "program_scopes.json"


def spans_to_chrome(spans: list[Span]) -> list[dict]:
    """Span records → Chrome complete events (+ process_name metadata).

    Each distinct span ``process`` label becomes a pid row so coordinator
    and worker timelines render as separate tracks of ONE stitched trace.
    """
    pids: dict[str, int] = {}
    events: list[dict] = []
    for sp in spans:
        label = sp.process or "main"
        if label not in pids:
            pids[label] = len(pids) + 1
            events.append({
                "name": "process_name", "ph": "M", "pid": pids[label],
                "tid": 0, "args": {"name": label},
            })
        events.append({
            "name": sp.name,
            "cat": "colearn",
            "ph": "X",
            "ts": sp.t_wall * 1e6,                 # micros on the wall clock
            "dur": sp.duration_s * 1e6,
            "pid": pids[label],
            "tid": 0,
            "args": {
                **sp.attrs,
                "trace_id": sp.trace_id,
                "span_id": sp.span_id,
                "parent_id": sp.parent_id,
            },
        })
    return events


def write_trace(path: str, spans: list[Span],
                metrics: Optional[dict] = None,
                dropped_spans: int = 0) -> str:
    """Write the Chrome-trace JSON file; returns ``path``."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    doc = {
        "traceEvents": spans_to_chrome(spans),
        "displayTimeUnit": "ms",
        "otherData": {
            "format_version": TRACE_VERSION,
            "num_spans": len(spans),
            "dropped_spans": dropped_spans,
        },
    }
    if metrics:
        doc["otherData"]["metrics"] = metrics
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)                  # readers never see a torn file
    return path


def load_trace(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if "traceEvents" not in doc:
        raise ValueError(f"{path}: not a Chrome-trace JSON (no traceEvents)")
    return doc


def trace_spans(doc: dict) -> list[Span]:
    """Reconstruct span records from a loaded trace (the JSON round-trip
    inverse of :func:`spans_to_chrome`)."""
    names = {
        ev["pid"]: ev["args"]["name"]
        for ev in doc["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "process_name"
    }
    spans = []
    for ev in doc["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args", {}))
        spans.append(Span.from_dict({
            "name": ev["name"],
            "trace_id": args.pop("trace_id", ""),
            "span_id": args.pop("span_id", ""),
            "parent_id": args.pop("parent_id", None),
            "process": names.get(ev["pid"], str(ev.get("pid", ""))),
            "t_wall": ev["ts"] / 1e6,
            "duration_s": ev.get("dur", 0.0) / 1e6,
            "attrs": args,
        }))
    return spans


def default_trace_path(trace_dir: str, name: str) -> str:
    return os.path.join(trace_dir, f"{name}_trace.json")


def write_tracer(trace_dir: str, name: str, tracer: Tracer,
                 metrics: Optional[dict] = None) -> str:
    return write_trace(default_trace_path(trace_dir, name),
                       tracer.snapshot(), metrics=metrics,
                       dropped_spans=tracer.dropped)


# ---------------------------------------------------------------- summary ----
def summarize_trace(doc: dict, root: str = "round") -> str:
    """Per-phase time breakdown of a trace, as printable text.

    Phases aggregate by span name; the denominator for the percentage
    column is the total time under ``root`` spans when any exist (so
    phase percentages read as "share of round wall time"), otherwise the
    overall traced extent.

    Fleetsim sweep traces are understood natively: with the default root
    and no ``round`` spans present, the root falls back to
    ``fleet_round``, and the per-chunk ``train_chunk`` children get a
    dispatch-rate line (chunks/s and clients/s at the chunk size carried
    in the ``train_chunks`` span attrs) instead of rendering as one
    opaque block.
    """
    spans = trace_spans(doc)
    if not spans:
        return "(empty trace)"
    by_name: dict[str, list[Span]] = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    if root == "round" and "round" not in by_name and (
            "fleet_round" in by_name):
        root = "fleet_round"
    if root == "round" and "round" not in by_name and (
            "async.aggregate" in by_name):
        # Buffered-async traces have no sync rounds; percentages read as
        # "share of aggregation wall time" instead.
        root = "async.aggregate"
    roots = by_name.get(root, [])
    if roots:
        denom = sum(sp.duration_s for sp in roots)
        denom_label = f"{len(roots)} {root} span(s)"
    else:
        t0 = min(sp.t_wall for sp in spans)
        t1 = max(sp.t_wall + sp.duration_s for sp in spans)
        denom = t1 - t0
        denom_label = "traced extent"
    denom = max(denom, 1e-12)
    procs = sorted({sp.process for sp in spans})
    lines = [
        f"trace: {len(spans)} spans over {len(procs)} process(es): "
        + ", ".join(procs),
        f"denominator: {denom:.6f} s ({denom_label})",
        "",
        f"{'phase':<28}{'count':>7}{'total_s':>12}{'mean_ms':>12}"
        f"{'max_ms':>12}{'pct':>8}",
    ]
    rows = []
    for phase, group in by_name.items():
        total = sum(sp.duration_s for sp in group)
        durs = [sp.duration_s for sp in group]
        rows.append((total, phase, len(group),
                     total / len(group) * 1e3, max(durs) * 1e3))
    for total, phase, n, mean_ms, max_ms in sorted(rows, reverse=True):
        lines.append(
            f"{phase:<28}{n:>7}{total:>12.4f}{mean_ms:>12.3f}"
            f"{max_ms:>12.3f}{100.0 * total / denom:>7.1f}%"
        )
    # Coverage: share of root-span time accounted for by their direct
    # children — the acceptance number for "spans cover the round".
    if roots:
        root_ids = {sp.span_id for sp in roots}
        child_t = sum(sp.duration_s for sp in spans
                      if sp.parent_id in root_ids)
        lines.append("")
        lines.append(
            f"phase coverage of {root} time: "
            f"{100.0 * min(1.0, child_t / denom):.1f}%"
        )
    # Fleetsim chunked-vmap sweep: dispatch-rate stats for the chunk loop.
    chunks = by_name.get("train_chunk", [])
    if chunks:
        chunk_t = max(sum(sp.duration_s for sp in chunks), 1e-12)
        # Total clients through the loop: the wrapper span carries the
        # per-round cohort in its attrs.
        cohort = sum(int(sp.attrs.get("cohort") or 0)
                     for sp in by_name.get("train_chunks", []))
        lines.append("")
        lines.append(
            f"fleetsim sweep: {len(chunks)} chunk dispatch(es), "
            f"{len(chunks) / chunk_t:.1f} chunks/s "
            f"(mean {chunk_t / len(chunks) * 1e3:.3f} ms/chunk)")
        if cohort:
            lines.append(
                f"fleetsim sweep: {cohort} client(s) at "
                f"{cohort / chunk_t:.0f} clients/s through the chunk loop")
    # Buffered-async runs: the observatory's version-lineage spans.  Each
    # fold_update is parented on its update's dispatch_train context, so
    # "stitched" counts how many folds joined a dispatch→train trace.
    aggs = by_name.get("async.aggregate", [])
    folds = by_name.get("fold_update", [])
    if aggs or folds:
        lines.append("")
        if aggs:
            agg_t = max(sum(sp.duration_s for sp in aggs), 1e-12)
            k_mean = (sum(int(sp.attrs.get("buffer_size") or 0)
                          for sp in aggs) / len(aggs))
            lines.append(
                f"async plane: {len(aggs)} aggregation(s) at "
                f"{len(aggs) / agg_t:.2f} folds/s (K mean {k_mean:.1f})")
        if folds:
            folded = [sp for sp in folds
                      if sp.attrs.get("outcome") == "folded"]
            stitched = sum(1 for sp in folds if sp.parent_id)
            lines.append(
                f"async lineage: {len(folded)} update(s) folded, "
                f"{len(folds) - len(folded)} discarded; "
                f"{stitched}/{len(folds)} stitched to dispatch spans")
            taus = sorted(float(sp.attrs.get("tau") or 0.0)
                          for sp in folded)
            if taus:
                def _q(p: float) -> float:
                    return taus[min(len(taus) - 1, int(p * len(taus)))]

                waits = [float(sp.attrs.get("buffer_wait_s") or 0.0)
                         for sp in folded]
                lines.append(
                    f"async staleness: p50 {_q(0.50):.0f}   "
                    f"p90 {_q(0.90):.0f}   p99 {_q(0.99):.0f}   "
                    f"mean buffer wait "
                    f"{sum(waits) / len(waits) * 1e3:.1f} ms")
    # Convergence observatory: aggregate/apply/server_update spans carry
    # conv_* attrs only when the run folded updates under --learn-observe.
    conv = [sp for spans in by_name.values() for sp in spans
            if sp.attrs.get("conv_update_norm") is not None]
    if conv:
        conv.sort(key=lambda sp: sp.t_wall)
        norms = [float(sp.attrs["conv_update_norm"]) for sp in conv]
        trends = [str(sp.attrs.get("conv_trend") or "") for sp in conv]
        census: dict[str, int] = {}
        for t in trends:
            if t:
                census[t] = census.get(t, 0) + 1
        census_s = " ".join(f"{k}={census[k]}" for k in sorted(census))
        lines.append("")
        lines.append(
            f"learning: {len(conv)} observed fold(s), update norm "
            f"{norms[0]:.3e} -> {norms[-1]:.3e} (max {max(norms):.3e})"
            + (f"; trend {census_s}" if census_s else ""))
    metrics = doc.get("otherData", {}).get("metrics")
    if metrics:
        lines.append("")
        lines.append("metrics:")
        for k in sorted(metrics):
            lines.append(f"  {k}: {json.dumps(metrics[k], sort_keys=True)}")
    return "\n".join(lines)


# ------------------------------------------------- device time by scope ----
def write_program_scopes(profile_dir: str) -> str:
    """``telemetry.program_scopes()`` as ``<profile_dir>/program_scopes.json``:
    per program, the distinct scopes and each instruction's index among
    them.  Builds the tables (a load or a build of each executable): call
    it once the profiler has stopped, never inside a round."""
    programs = {}
    for module, table in program_scopes().items():
        scopes = sorted(set(table.values()))
        index = {scope: i for i, scope in enumerate(scopes)}
        programs[module] = {
            "scopes": [[scope.phase, list(scope.path)] for scope in scopes],
            "instructions": {name: index[scope]
                             for name, scope in table.items()},
        }
    path = os.path.join(profile_dir, SCOPES_FILE)
    os.makedirs(profile_dir, exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump({"format_version": TRACE_VERSION, "programs": programs}, f)
    os.replace(path + ".tmp", path)
    return path


def load_program_scopes(path: str) -> dict[str, dict[str, Scope]]:
    with open(path) as f:
        doc = json.load(f)
    tables = {}
    for module, program in doc["programs"].items():
        scopes = [Scope(phase, tuple(names))
                  for phase, names in program["scopes"]]
        tables[module] = {name: scopes[i]
                          for name, i in program["instructions"].items()}
    return tables


def device_seconds_by_scope(ops: list, modules: list, tables: dict) -> dict:
    """One chip's ``XLA Ops`` and ``XLA Modules`` events, each ``(name,
    start_ns, duration_ns)`` as the profile gives them, as self seconds by
    ``(program, Scope)``; ``(program, None)`` holds what ran under an
    instruction name the program's table lacks.  An operation belongs to
    the execution it began in, and is looked up in that program's table
    (two programs share instruction names); a container (a ``while``)
    counts less its children.  Programs without a table are left out."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    totals: dict = {}
    stack: list = []          # [end_ns, key, own_ns] of the open operations

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _, key, own = stack.pop()
            totals[key] = totals.get(key, 0.0) + max(own, 0.0) / 1e9

    for text, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        i = bisect.bisect_right(starts, start)
        if not i or start >= modules[i - 1][1] + modules[i - 1][2]:
            continue
        program = modules[i - 1][0].split("(", 1)[0]
        table = tables.get(program)
        if table is None:
            continue
        if stack:
            stack[-1][2] -= dur
        name = text.partition(" = ")[0].split(" ", 1)[0].lstrip("%")
        stack.append([start + dur, (program, table.get(name)), dur])
    close(float("inf"))
    return totals


def summarize_profile(profile_dir: str, top: int = 15,
                      depth: int = 4) -> str:
    """Device self time of a ``--profile-dir`` run by phase and by the top
    scope paths (their first ``depth`` names), per program, on the first
    chip of the newest profile under ``profile_dir``."""
    from jax.profiler import ProfileData

    profiles = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not profiles:
        raise ValueError(f"{profile_dir}: no profile (*.xplane.pb) inside")
    tables = load_program_scopes(os.path.join(profile_dir, SCOPES_FILE))
    # A chip's plane is the one with a line of operations (a TPU's profile
    # has other ``/device:`` planes beside the chips').
    chips = sorted(
        (plane.name, {line.name: line for line in plane.lines})
        for plane in ProfileData.from_file(profiles[-1]).planes
        if plane.name.startswith("/device:")
        and any(line.name == "XLA Ops" for line in plane.lines))
    lines = [f"profile: {profiles[-1]}"]
    if not chips:
        return "\n".join(lines + ["(no device plane: nothing ran on a chip)"])
    chip, by_line = chips[0]
    ops, modules = (
        [(e.name, float(e.start_ns), float(e.duration_ns))
         for e in by_line[name].events] if name in by_line else []
        for name in ("XLA Ops", "XLA Modules"))
    lines.append(f"device time by scope on {chip}")
    return "\n".join(lines + render_scope_seconds(
        device_seconds_by_scope(ops, modules, tables), top, depth))


def render_scope_seconds(totals: dict, top: int = 15,
                         depth: int = 4) -> list[str]:
    lines = []
    for program in sorted({program for program, _ in totals}):
        mine = {scope: t for (p, scope), t in totals.items() if p == program}
        whole = max(sum(mine.values()), 1e-12)
        lines += ["", f"{program}: {whole:.6f} s in its table's programs, "
                      f"{100.0 * (1 - mine.get(None, 0.0) / whole):.1f}% "
                      "under a known instruction"]
        by_phase: dict = {}
        by_path: dict = {}
        for scope, t in mine.items():
            if scope is None:
                continue
            by_phase[scope.phase] = by_phase.get(scope.phase, 0.0) + t
            key = "/".join(scope.path[:depth]) or "(no name)"
            by_path[key] = by_path.get(key, 0.0) + t
        lines.append("  by phase: " + "   ".join(
            f"{phase} {t:.6f} s ({100.0 * t / whole:.1f}%)"
            for phase, t in sorted(by_phase.items(), key=lambda kv: -kv[1])))
        for key, t in sorted(by_path.items(), key=lambda kv: -kv[1])[:top]:
            lines.append(f"  {t:>12.6f} s{100.0 * t / whole:>7.1f}%  {key}")
    return lines
