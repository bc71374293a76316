"""The program's own spans on the jax profiler's clock, and its compile
counters: ``telemetry/tracer.py``, ``lifecycle.py``, ``runtime.py`` and
their call sites in ``fed/engine.py``.  Counts and structure; the only
thing asserted of a time is its order."""

import contextlib
import dataclasses
import glob
import importlib.util
import inspect
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.fed import engine
from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.telemetry import runtime, tracer
from colearn_federated_learning_tpu.telemetry.registry import MetricsRegistry
from colearn_federated_learning_tpu.utils.config import get_config

ROUND_SPANS = {"fit", "round", "enqueue", "sync_metrics", "bookkeeping",
               "log", "evaluate"}


def tiny_config(**run_kw):
    cfg = get_config("mnist_mlp_fedavg")
    return cfg.replace(
        data=dataclasses.replace(cfg.data, dataset="mnist_tiny",
                                 num_clients=4),
        fed=dataclasses.replace(cfg.fed, rounds=2, local_steps=2,
                                batch_size=8, cohort_size=4),
        run=dataclasses.replace(cfg.run, backend="cpu", eval_every=1,
                                name="spans", **run_kw),
    )


@contextlib.contextmanager
def profiler_session(directory):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(directory, names):
    """(name, start_ns, end_ns) of the host plane's events so named, in
    order of start, containers first."""
    [path] = glob.glob(os.path.join(
        str(directory), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines for e in line.events
                   if e.name in names)
    return sorted(out, key=lambda e: (e[1], -e[2]))


def counters(prefix="telemetry."):
    return {k: v for k, v in telemetry.get_registry().snapshot().items()
            if k.startswith(prefix)}


def test_fit_in_a_profiler_session_annotates_and_records(tmp_path):
    learner = FederatedLearner.from_config(tiny_config())
    learner.fit(rounds=1)                       # compile outside the window
    assert telemetry.get_tracer().snapshot() == []   # nothing kept
    with profiler_session(tmp_path):
        learner.fit(rounds=2, log_fn=lambda rec: None)
    spans = sorted(telemetry.get_tracer().snapshot(),
                   key=lambda s: (s.start_ns, -s.end_ns))
    assert {s.name for s in spans} == ROUND_SPANS
    assert [s.name for s in spans].count("round") == 2
    # The same spans, in the same order, on the profile's host plane.
    events = host_events(tmp_path, ROUND_SPANS)
    assert [e[0] for e in events] == [s.name for s in spans]
    # Nested there as in the buffer: each event lies inside its parent's.
    by_id = {s.span_id: i for i, s in enumerate(spans)}
    parents = {"fit": None, "round": "fit"}
    for i, s in enumerate(spans):
        if s.parent_id is None:
            assert s.name == "fit"
            continue
        parent = by_id[s.parent_id]
        assert spans[parent].name == parents.get(s.name, "round")
        assert events[parent][1] <= events[i][1]
        assert events[i][2] <= events[parent][2]
        assert spans[parent].start_ns <= s.start_ns <= s.end_ns <= (
            spans[parent].end_ns)
    # Once the session is closed, a fit() keeps none of them.
    learner.fit(rounds=1, log_fn=lambda rec: None)
    assert telemetry.get_tracer().snapshot() == []
    assert telemetry.get_tracer().enabled is False


def test_round_spans_tile_the_round(tmp_path):
    learner = FederatedLearner.from_config(tiny_config())
    learner.fit(rounds=1)
    with profiler_session(tmp_path):
        learner.fit(rounds=3, log_fn=lambda rec: None)
    spans = telemetry.get_tracer().snapshot()
    rounds = [s for s in spans if s.name == "round"]
    assert len(rounds) == 3
    for r in rounds:
        children = sorted((s for s in spans if s.parent_id == r.span_id),
                          key=lambda s: s.start_ns)
        assert [s.name for s in children] == [
            "enqueue", "sync_metrics", "bookkeeping", "evaluate", "log"]
        # One after the other inside the round, no overlap.  (How much
        # of the round they cover is a time: the chip's trace says, as
        # `host_gap_ms_per_round`'s remainder, not a shared CPU.)
        assert r.start_ns <= children[0].start_ns
        assert children[-1].end_ns <= r.end_ns
        for a, b in zip(children, children[1:]):
            assert a.end_ns <= b.start_ns


def test_recording_fit_builds_the_round_program_once(tmp_path):
    before = counters().get("telemetry.compile_total{fn=engine.round}", 0)
    learner = FederatedLearner.from_config(
        tiny_config(trace_dir=str(tmp_path)))
    records = []
    learner.fit(log_fn=records.append)
    assert learner._round_fn.compiles == 1
    assert counters()["telemetry.compile_total{fn=engine.round}"] == before + 1
    assert len(records) == 2
    for rec in records:
        assert "flops_per_round" not in rec
        assert {"phase_update_s", "phase_sync_s", "phase_eval_s"} <= set(rec)
    # The trace file holds this learner's spans, the root included.
    doc = telemetry.load_trace(learner.last_trace_path)
    names = [s.name for s in telemetry.trace_spans(doc)]
    assert set(names) == ROUND_SPANS
    assert names.count("fit") == 1 and names.count("round") == 2


def test_buffer_is_one_learners_newest_window(tmp_path):
    first = FederatedLearner.from_config(
        tiny_config(trace_dir=str(tmp_path / "a")))
    second = FederatedLearner.from_config(
        tiny_config(trace_dir=str(tmp_path / "b")))
    buffer = telemetry.get_tracer()
    first.fit(rounds=1)
    first.fit(rounds=1)                     # one learner's calls add up
    assert [s.name for s in buffer.snapshot()].count("fit") == 2
    second.fit(rounds=1)                    # another learner starts afresh
    assert [s.name for s in buffer.snapshot()].count("fit") == 1
    with open(second.last_trace_path) as f:
        assert json.load(f)["otherData"]["num_spans"] == len(
            buffer.snapshot())
    # Building a learner is outside every window.
    FederatedLearner.from_config(tiny_config())
    assert "from_config" not in {s.name for s in buffer.snapshot()}


def test_from_config_span_is_annotated_and_timed(tmp_path):
    with profiler_session(tmp_path):
        FederatedLearner.from_config(tiny_config())
    events = host_events(tmp_path, {"from_config", "h2d_transfer"})
    assert [e[0] for e in events] == ["from_config", "h2d_transfer"]
    assert events[0][1] <= events[1][1] and events[1][2] <= events[0][2]
    gauges = telemetry.get_registry().snapshot()
    assert gauges["engine.from_config_s"] >= gauges["engine.h2d_transfer_s"]
    assert gauges["engine.from_config_s"] > 0


def test_compile_seconds_come_from_the_first_call_only():
    def seconds():
        snap = counters("telemetry.compile_seconds")
        return (snap.get("telemetry.compile_seconds{fn=engine.round}", 0.0),
                snap.get("telemetry.compile_seconds{fn=engine.eval}", 0.0))

    start = seconds()
    learner = FederatedLearner.from_config(tiny_config())
    assert seconds() == start               # nothing was called yet
    learner.fit(rounds=1)
    first = seconds()
    assert first[0] > start[0] and first[1] > start[1]
    learner.fit(rounds=2)
    learner.evaluate()
    assert seconds() == first               # later calls add nothing


def fresh_jit(salt):
    """A program no other test compiles, so that this process has to ask
    the persistent cache for it."""
    return jax.jit(lambda x: x * salt + 0.3141)


def test_cache_events_count_under_the_tracked_call():
    reg = MetricsRegistry()
    tracked = runtime.CompileTracker(fresh_jit(1.0625), name="t",
                                     registry=reg)
    tracked(jnp.ones((3,)))
    snap = reg.snapshot()
    asked = (snap.get("telemetry.cache_hit_total{fn=t}", 0)
             + snap.get("telemetry.cache_miss_total{fn=t}", 0))
    assert asked == 1                       # loaded, or built and written
    assert snap["telemetry.compile_seconds{fn=t}"] > 0
    tracked(jnp.ones((3,)))                 # runs the executable it has
    assert reg.snapshot() == snap


def test_compile_outside_a_tracked_call_is_not_counted():
    reg = MetricsRegistry()
    with runtime.tracked_call("warm", registry=reg):
        pass                                # the listener is registered
    before = counters()
    fresh_jit(1.1875)(jnp.ones((3,)) * 2)   # the caller's own programs
    assert reg.snapshot() == {}
    assert counters() == before


def test_tracked_calls_nest_and_restore():
    reg = MetricsRegistry()
    x = jnp.ones((3,))          # made out here: an eager op compiles too
    with runtime.tracked_call("outer", registry=reg):
        with runtime.tracked_call("inner", registry=reg):
            fresh_jit(1.3125)(x)
        fresh_jit(1.4375)(x)
    fresh_jit(1.5625)(x)
    snap = reg.snapshot()
    for fn in ("outer", "inner"):
        assert (snap.get(f"telemetry.cache_hit_total{{fn={fn}}}", 0)
                + snap.get(f"telemetry.cache_miss_total{{fn={fn}}}", 0)) == 1


def test_span_carries_its_monotonic_clock():
    tr = tracer.Tracer(process="t")
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    inner, outer = tr.snapshot()
    assert isinstance(inner.start_ns, int) and isinstance(inner.end_ns, int)
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    wire = json.loads(json.dumps(inner.to_dict()))
    assert (wire["start_ns"], wire["end_ns"]) == (inner.start_ns,
                                                 inner.end_ns)
    back = tracer.Span.from_dict(wire)
    assert (back.start_ns, back.end_ns) == (inner.start_ns, inner.end_ns)
    assert back.duration_s == inner.duration_s
    # A form without the clock (a loaded trace file) keeps its duration.
    loaded = tracer.Span.from_dict({"name": "x", "trace_id": "a",
                                    "span_id": "b", "duration_s": 0.25})
    assert loaded.ended and loaded.duration_s == pytest.approx(0.25)


def test_tracer_does_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    tracer._annotation.cache_clear()
    try:
        assert tracer.profiler_session_open() is False
        tr = tracer.Tracer(process="t")
        with tr.span("x") as sp:
            pass
        assert sp.ended and tr.snapshot() == [sp]
    finally:
        tracer._annotation.cache_clear()


def test_profiler_session_is_seen(tmp_path):
    assert tracer.profiler_session_open() is False
    with profiler_session(tmp_path):
        assert tracer.profiler_session_open() is True
    assert tracer.profiler_session_open() is False


def test_engine_has_no_barrier_outside_data_placement():
    source = inspect.getsource(engine)
    placement = inspect.getsource(FederatedLearner._place_data)
    assert source.count("block_until_ready") == placement.count(
        "block_until_ready") == 2
    assert importlib.util.find_spec(
        "colearn_federated_learning_tpu.utils.profiling") is None
