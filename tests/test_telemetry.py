"""telemetry/: span nesting + timing, Chrome-trace round-trip, metrics
registry, trace-id propagation through a loopback socket federation, and
the MetricsLogger phase-field / stream-ownership fixes."""

import io
import json
import time

import pytest

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.metrics import MetricsLogger
from colearn_federated_learning_tpu.telemetry.registry import (
    Histogram,
    MetricsRegistry,
)
from colearn_federated_learning_tpu.telemetry.lifecycle import RoundProfiler
from colearn_federated_learning_tpu.telemetry.tracer import Tracer


# ------------------------------------------------------------- tracer ----
def test_span_nesting_and_parent_ids():
    tr = Tracer(process="t")
    with tr.span("round", round=0) as outer:
        with tr.span("aggregate") as inner:
            assert tr.current_context() == inner.context
        assert tr.current_context() == outer.context
    assert tr.current_context() is None
    spans = {s.name: s for s in tr.snapshot()}
    assert spans["aggregate"].parent_id == spans["round"].span_id
    assert spans["aggregate"].trace_id == spans["round"].trace_id
    assert spans["round"].parent_id is None
    assert spans["round"].attrs == {"round": 0}


def test_span_timing_monotonic_and_contained():
    tr = Tracer(process="t")
    with tr.span("outer"):
        with tr.span("inner"):
            time.sleep(0.01)
    inner, outer = (
        {s.name: s for s in tr.snapshot()}[k] for k in ("inner", "outer")
    )
    assert inner.ended and outer.ended
    assert inner.duration_s >= 0.01
    assert outer.duration_s >= inner.duration_s


def test_disabled_tracer_still_times_but_records_nothing():
    tr = Tracer(process="t", enabled=False)
    with tr.span("x") as sp:
        time.sleep(0.005)
    assert sp.duration_s >= 0.005
    assert tr.snapshot() == []


def test_span_buffer_bounded_counts_drops():
    tr = Tracer(process="t", max_spans=2)
    for _ in range(4):
        with tr.span("s"):
            pass
    assert len(tr.snapshot()) == 2 and tr.dropped == 2


def test_remote_parent_and_adopt_roundtrip():
    coord, worker = Tracer(process="coord"), Tracer(process="worker-0")
    with coord.span("round") as round_sp:
        ctx = coord.current_context()
        with worker.capture() as captured:
            with worker.span("worker.train", parent=ctx):
                pass
        wire = [s.to_dict() for s in captured]
        coord.adopt(json.loads(json.dumps(wire)))   # through JSON, as on the wire
    spans = {s.name: s for s in coord.snapshot()}
    assert spans["worker.train"].trace_id == round_sp.trace_id
    assert spans["worker.train"].parent_id == round_sp.span_id
    assert spans["worker.train"].process == "worker-0"
    # malformed entries are skipped, not fatal
    assert coord.adopt([{"nonsense": 1}, None]) == 0


# ----------------------------------------------------------- registry ----
def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    for v in range(100):
        reg.histogram("h").observe(float(v))
    snap = reg.snapshot()
    assert snap["c"] == 5.0
    assert snap["g"] == 2.5
    h = snap["h"]
    assert h["count"] == 100 and h["min"] == 0.0 and h["max"] == 99.0
    assert 40.0 <= h["p50"] <= 60.0
    with pytest.raises(TypeError):
        reg.gauge("c")                   # kind mismatch on an existing name
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.reset()
    assert reg.snapshot() == {}


def test_histogram_thinning_keeps_exact_count_sum():
    h = Histogram("h", max_samples=64)
    n = 10_000
    for v in range(n):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == n
    assert s["sum"] == float(n * (n - 1) // 2)
    assert s["min"] == 0.0 and s["max"] == float(n - 1)
    # the deterministic thinning keeps quantiles roughly in place
    assert 0.3 * n <= s["p50"] <= 0.7 * n


# ------------------------------------------------- chrome-trace export ----
def test_chrome_trace_schema_roundtrip(tmp_path):
    tr = Tracer(process="engine")
    with tr.span("round", round=0):
        with tr.span("enqueue"):
            pass
    path = telemetry.write_trace(
        str(tmp_path / "t_trace.json"), tr.snapshot(), metrics={"m": 1.0}
    )
    doc = telemetry.load_trace(path)
    events = doc["traceEvents"]
    x = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in x} == {"round", "enqueue"}
    for e in x:                          # Chrome-trace complete events
        assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["dur"] >= 0
    assert any(e["name"] == "process_name" for e in meta)
    assert doc["otherData"]["metrics"] == {"m": 1.0}
    # inverse: spans survive the round-trip with ids intact
    back = {s.name: s for s in telemetry.trace_spans(doc)}
    orig = {s.name: s for s in tr.snapshot()}
    assert back["enqueue"].parent_id == orig["round"].span_id
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        telemetry.load_trace(str(bad))


def test_summarize_trace_reports_phases_and_coverage():
    tr = Tracer(process="engine")
    with tr.span("round"):
        with tr.span("enqueue"):
            time.sleep(0.01)
    text = telemetry.summarize_trace(
        {"traceEvents": telemetry.spans_to_chrome(tr.snapshot())}
    )
    assert "enqueue" in text and "phase coverage" in text


# ------------------------------------- propagation through the sockets ----
def test_trace_propagation_loopback_federation():
    from colearn_federated_learning_tpu.comm.broker import MessageBroker
    from colearn_federated_learning_tpu.comm.coordinator import (
        FederatedCoordinator,
    )
    from colearn_federated_learning_tpu.comm.worker import DeviceWorker
    from colearn_federated_learning_tpu.utils.config import (
        DataConfig,
        ExperimentConfig,
        FedConfig,
        ModelConfig,
        RunConfig,
    )

    cfg = ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=3, partition="iid"),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=16, depth=2),
        fed=FedConfig(strategy="fedavg", rounds=1, cohort_size=0,
                      local_steps=2, batch_size=8, lr=0.1),
        run=RunConfig(name="trace_test", backend="cpu"),
    )
    with MessageBroker() as broker:
        workers = [DeviceWorker(cfg, i, broker.host, broker.port).start()
                   for i in range(3)]
        try:
            coord = FederatedCoordinator(cfg, broker.host, broker.port,
                                         round_timeout=60.0)
            coord.enroll(min_devices=3, timeout=20.0)
            rec = coord.run_round()
            coord.close()
        finally:
            for w in workers:
                w.stop()

    spans = coord.tracer.snapshot()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    round_sp = by_name["round"][0]
    # the worker's train spans were shipped back and stitched into the
    # coordinator's trace under the SAME trace id
    trains = by_name["worker.train"]
    assert len(trains) == rec["completed"]
    for s in trains:
        assert s.trace_id == round_sp.trace_id
        assert s.process.startswith("worker-")
        assert s.duration_s > 0
    # worker child spans rode along too
    assert any(s.name == "local_train" for s in spans)
    # and none of it leaked into the round record (JSONL purity)
    assert "trace_spans" not in json.dumps(rec)
    assert rec["phase_broadcast_collect_s"] > 0
    assert rec["phase_aggregate_s"] > 0


# -------------------------------------------------------- MetricsLogger ----
def test_metrics_logger_never_closes_external_stream():
    buf = io.StringIO()
    with MetricsLogger(stream=buf, name="t") as m:
        m.log({"round": 0, "x": 1.0})
    assert not buf.closed                 # caller still owns the stream
    rec = json.loads(buf.getvalue().splitlines()[0])
    assert rec["round"] == 0 and rec["name"] == "t"


def test_metrics_logger_rejects_path_plus_stream(tmp_path):
    with pytest.raises(ValueError):
        MetricsLogger(path=str(tmp_path / "m.jsonl"), stream=io.StringIO())


def test_metrics_logger_closes_tensorboard():
    closed = {"flush": 0, "close": 0}

    class FakeTB:
        def scalar(self, *a, **kw):
            pass

        def flush(self):
            closed["flush"] += 1

        def close(self):
            closed["close"] += 1

    m = MetricsLogger(name="t")
    m._tb = FakeTB()
    m.log({"round": 0, "acc": 0.5})
    m.close()
    assert closed["flush"] >= 1 and closed["close"] == 1
    assert m._tb is None


def test_metrics_logger_jsonl_has_phase_fields(tmp_path):
    """engine.fit's per-round records — and therefore the JSONL — carry
    the span-timed phase durations."""
    import dataclasses

    from colearn_federated_learning_tpu.fed.engine import FederatedLearner
    from colearn_federated_learning_tpu.utils.config import get_config

    cfg = get_config("mnist_mlp_fedavg")
    cfg = cfg.replace(
        data=dataclasses.replace(cfg.data, dataset="mnist_tiny",
                                 num_clients=4),
        fed=dataclasses.replace(cfg.fed, rounds=1, local_steps=1,
                                batch_size=8, cohort_size=4),
        run=dataclasses.replace(cfg.run, backend="cpu", eval_every=1,
                                name="phase_test"),
    )
    path = str(tmp_path / "m.jsonl")
    learner = FederatedLearner.from_config(cfg)
    with MetricsLogger(path=path, name="phase_test") as m:
        learner.fit(log_fn=m.log)
    rec = json.loads(open(path).read().splitlines()[0])
    assert rec["phase_update_s"] > 0
    assert "phase_sync_s" in rec and "phase_eval_s" in rec
    assert rec["round_time_s"] >= rec["phase_update_s"]


# ------------------------------------------------- profiler satellite ----
def test_round_profiler_active_is_public():
    p = RoundProfiler(None)               # disabled: no profile dir
    assert p.active is False
    p.before_round(0)
    assert p.active is False              # still disabled
    p.close()
