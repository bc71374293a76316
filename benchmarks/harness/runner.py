"""One run of one cell: set-up, warm-up, the measured window, the result
line.

What is timed is the path users run: ``FederatedLearner.from_config`` and
``learner.fit(rounds=eval_every)`` again and again, each call a *chunk* of
``eval_every`` synchronous rounds ending in the evaluation ``fit()`` makes
on its last round, with a ``log_fn`` that appends every record to a JSONL
file as ``colearn train --log-file`` does.  The window is whole chunks: a
chunk starts while less than ``--seconds`` have passed, the one in
progress finishes, and rates divide by the time really taken.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Optional

from benchmarks.harness import probe, xplane
from benchmarks.harness.spec import Bench

FIT_SPAN = "fit"
# A traced window stops starting chunks after this long (it is one chunk at
# least): traces are large, reading one back takes set-up-sized time, and
# tracing slows the host.
TRACE_SECONDS = 1.0


class NoDevice(RuntimeError):
    """jax does not offer the platform and chip count the cell asks for."""


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader may read."""

    records: list[dict]            # the window's round records, in order
    window_s: float                # host clock over the whole chunks
    samples_per_round: int
    chips: int
    device_kind: str
    memory: list[dict]             # memory_stats() per chip, after the window
    config: dict                   # the configuration file
    bench: Bench
    trace: Optional[xplane.Trace]  # None when the run was not traced

    @property
    def rounds(self) -> int:
        return len(self.records)


@contextlib.contextmanager
def watch_compiles():
    """jax's own compile events over a block (copied from
    ``chip_smoke.py``): executables built or loaded, the seconds that
    took (a persistent-cache hit counts its load time), and the cache's
    hits and misses."""
    from jax import monitoring

    seen = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
            "cache_misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1
            seen["compile_s"] += duration

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        monitoring.unregister_event_listener(on_event)
        monitoring.unregister_event_duration_listener(on_duration)


def say(**line) -> None:
    print(json.dumps(line), flush=True)


def claim_devices(chips: int, platform: str):
    """The devices of this process, which must be exactly the cell's."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) != chips:
        raise NoDevice(
            f"cell needs {chips} x {platform}, jax.devices() = {devices}")
    return devices


def measure(learner, chunk_rounds: int, seconds: float, log_fn) -> dict:
    """Whole chunks of ``fit()`` for ``seconds``; returns what happened."""
    import jax

    first = len(learner.history)
    error, chunks = None, 0
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        try:
            with jax.profiler.TraceAnnotation(FIT_SPAN):
                learner.fit(rounds=chunk_rounds, log_fn=log_fn)
        except Exception as exc:  # noqa: BLE001 - counted, reported, fatal
            error = f"{type(exc).__name__}: {exc}"
            break
        chunks += 1
    elapsed = time.perf_counter() - start
    records = learner.history[first:]
    bad = sum(1 for r in records
              if not (math.isfinite(r["train_loss"])
                      and math.isfinite(r.get("eval_loss", 0.0))))
    # A round that raised was started and left no record.
    raised = 1 if error else 0
    return {"records": records, "elapsed_s": elapsed, "chunks": chunks,
            "attempted": len(records) + raised, "failed": bad + raised,
            "error": error}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, platform: str = "tpu") -> int:
    """Runs the cell and prints its lines; ``platform`` is ``"tpu"``
    except in the CPU rehearsals under ``benchmarks/tests``."""
    bench = Bench(root)
    cell = bench.workload(workload)
    config_doc = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    reference = bench.module("reference", config_doc["family"])

    import jax

    devices = claim_devices(cell["chips"], platform)
    # Set-up is timed from here: what precedes it (the interpreter, jax,
    # the TPU runtime) is neither the program's nor the benchmark's, and
    # on one machine it varied by 3.5 s from run to run, which alone
    # spreads a 40 s set-up by more than any bound may allow.  The
    # program's own imports come after, so they count.
    t_ready = time.perf_counter()

    from benchmarks.traffic import generate
    from colearn_federated_learning_tpu import telemetry
    from colearn_federated_learning_tpu.fed.engine import FederatedLearner
    from colearn_federated_learning_tpu.metrics import MetricsLogger
    from colearn_federated_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    # Every program of a run is worth keeping: the small ones (weight
    # initialisation, the probe) are paid for in set-up by every later run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(event="device", platform=devices[0].platform,
        kind=devices[0].device_kind, count=len(devices), jax=jax.__version__,
        compile_cache_dir=cache_dir, workload=workload, seed=seed,
        runtime_start_s=t_ready - t_start)
    phases = {"imports_s": time.perf_counter() - t_ready}

    def phase_done(name: str) -> None:
        phases[name] = time.perf_counter() - t_ready - sum(phases.values())

    config = generate.experiment_config(config_doc, traffic, seed)
    chunk_rounds = config.run.eval_every
    scratch = tempfile.mkdtemp(prefix="colearn_bench_")
    try:
        with watch_compiles() as setup_compiles, MetricsLogger(
                path=os.path.join(scratch, "rounds.jsonl"),
                name=workload) as logger:
            dataset = generate.dataset(config_doc, traffic, seed)
            phase_done("data_s")
            learner = FederatedLearner.from_config(config, dataset=dataset)
            phase_done("learner_s")
            parity = probe.parity(learner, reference, config_doc)
            phase_done("probe_s")
            learner.fit(rounds=1, log_fn=logger.log)       # warm-up
            phase_done("warm_up_s")
            setup_s = time.perf_counter() - t_ready
            say(event="setup", setup_s=setup_s, phases=phases, parity=parity,
                **setup_compiles)

            if trace:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(scratch, profiler_options=options)
            try:
                with watch_compiles() as window_compiles:
                    window = measure(
                        learner, chunk_rounds,
                        min(seconds, TRACE_SECONDS) if trace else seconds,
                        logger.log)
            finally:
                if trace:
                    jax.profiler.stop_trace()
        memory = [d.memory_stats() or {} for d in learner.devices]
        traced = None
        if trace:
            [path] = glob.glob(os.path.join(
                scratch, "plugins", "profile", "*", "*.xplane.pb"))
            traced = xplane.load(path, [FIT_SPAN])
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    records = window["records"]
    rounds = len(records)
    samples = learner.cohort_size * learner.num_steps * config.fed.batch_size
    round_compiles = telemetry.get_registry().counter(
        "telemetry.compile_total", labels={"fn": "engine.round"}).value
    say(event="window", rounds=rounds, chunks=window["chunks"],
        elapsed_s=window["elapsed_s"], error=window["error"],
        rounds_per_s=rounds / window["elapsed_s"],
        samples_per_round=samples, cohort=learner.cohort_size,
        round_compiles=round_compiles, **window_compiles,
        train_loss=[r["train_loss"] for r in learner.history],
        eval=[[r["round"], r["eval_loss"], r["eval_acc"]]
              for r in learner.history if "eval_loss" in r],
        memory_stats=memory)

    reading = Reading(
        records=records, window_s=window["elapsed_s"],
        samples_per_round=samples, chips=len(devices),
        device_kind=devices[0].device_kind, memory=memory,
        config=config_doc, bench=bench, trace=traced)
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(map(peak_bytes, memory), default=0),
    }
    result = {
        "correct": bool(
            parity["ok"] and window["error"] is None
            and window["failed"] == 0 and rounds > 0
            and window_compiles["compiles"] == 0 and round_compiles == 1
            and not any("recompiles" in r for r in learner.history)),
        "attempted": window["attempted"], "failed": window["failed"],
    }
    if trace:
        result["metrics"] = per_layer(bench, workload, reading)
        device["busy_s"] = traced.busy_s
        device["window_s"] = traced.window_s
        if traced.devices:
            first = min(traced.devices)
            result["breakdown"] = {
                "device_ops": xplane.top(xplane.self_times(xplane.clip(
                    traced.devices[first].ops, traced.window_ns))),
                "idle_gaps": xplane.top(xplane.idle_gaps(traced, first)),
            }
        result["correct"] = result["correct"] and device["busy_s"] > 0
    else:
        result["metrics"] = end_to_end(
            bench, workload, learner.history, rounds * samples,
            window["elapsed_s"], len(devices), setup_s)
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


def peak_bytes(stats: dict) -> int:
    """One chip's peak from its ``memory_stats()``.  On this runtime the
    space a running program reserves for its temporaries is counted apart
    from the live buffers (free = limit - reserved - in use, PERF.md
    section 5), so the peak is the two together, unless live buffers alone
    once stood higher (set-up)."""
    return max(stats.get("peak_bytes_in_use", 0),
               stats.get("bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def end_to_end(bench: Bench, workload: str, history: list[dict],
               examples: int, elapsed_s: float, chips: int,
               setup_s: float) -> dict:
    values = {
        "client_samples_per_s_per_chip": examples / elapsed_s / chips,
        "setup_s": setup_s,
    }
    # By round index, warm-up round included, so that a faster program
    # does not change which rounds are read.
    if len(history) >= 16:
        values["train_loss_r8_15"] = statistics.fmean(
            r["train_loss"] for r in history[8:16])
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench.metrics("end_to_end", workload)
            if m["name"] in values}


def per_layer(bench: Bench, workload: str, reading: Reading) -> dict:
    """Each of the cell's per-layer metrics from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench.metrics("per_layer", workload):
        value = bench.module("layer_metrics", m["name"]).read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list[str], root: str, t_start: float) -> int:
    import argparse

    parser = argparse.ArgumentParser(prog="benchmarks/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    try:
        return run_cell(root, args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start)
    except NoDevice as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 1
