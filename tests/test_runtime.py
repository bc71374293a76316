"""telemetry/runtime.py: the XLA-introspection + live-export half of the
observability plane — CompileTracker signature fingerprinting and
recompile attribution, AOT cost analysis, the Prometheus text exposition
(validated against a strict grammar oracle — the S4 wire-format
contract), the HTTP exporter round-trip, the JSONL event stream, and the
`colearn top` renderer."""

import json
import re
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from colearn_federated_learning_tpu.telemetry import runtime
from colearn_federated_learning_tpu.telemetry.registry import (
    MetricsRegistry,
)


def fresh_registry() -> MetricsRegistry:
    return MetricsRegistry()


# ------------------------------------------------------- signatures ------
def test_abstract_signature_ignores_host_scalar_values():
    a = runtime.abstract_signature((jnp.ones((4,)), 3), {})
    b = runtime.abstract_signature((jnp.ones((4,)), 99), {})
    assert a == b                      # int VALUE change: same cache entry
    c = runtime.abstract_signature((jnp.ones((4,)), 3.0), {})
    assert a != c                      # int -> float: a re-trace


def test_abstract_signature_sees_shape_dtype_structure():
    base = runtime.abstract_signature((jnp.ones((4,)),), {})
    assert base != runtime.abstract_signature((jnp.ones((8,)),), {})
    assert base != runtime.abstract_signature(
        (jnp.ones((4,), jnp.int32),), {})
    assert base != runtime.abstract_signature(
        ((jnp.ones((4,)), jnp.ones((4,))),), {})


# --------------------------------------------------- CompileTracker ------
def test_compile_tracker_counts_distinct_signatures():
    reg = fresh_registry()
    f = runtime.CompileTracker(jax.jit(lambda x: x * 2), name="t",
                               registry=reg)
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))                  # same signature: no new compile
    assert (f.compiles, f.recompiles) == (1, 0)
    f(jnp.ones((8,)))
    assert (f.compiles, f.recompiles) == (2, 1)
    snap = reg.snapshot()
    assert snap["telemetry.compile_total{fn=t}"] == 2
    assert snap["telemetry.recompile_total{fn=t,reason=shape}"] == 1


def test_compile_tracker_attributes_recompile_reasons():
    reg = fresh_registry()
    f = runtime.CompileTracker(jax.jit(lambda x: x), name="t",
                               registry=reg)
    f(jnp.ones((4,)))
    f(jnp.ones((4,), jnp.int32))       # dtype flip
    f((jnp.ones((4,), jnp.int32), jnp.ones((2,))))  # structure flip
    snap = reg.snapshot()
    assert snap["telemetry.recompile_total{fn=t,reason=dtype}"] == 1
    assert snap["telemetry.recompile_total{fn=t,reason=structure}"] == 1
    assert f.recompiles == 2


def test_compile_tracker_sees_placement_recompiles(mesh8):
    # Same shape, dtype and structure — but jit keys its executables on
    # argument placement too, and builds a second one when the operand
    # arrives laid over a mesh.  A signature alone cannot see that.
    from jax.sharding import NamedSharding, PartitionSpec as P

    reg = fresh_registry()
    f = runtime.CompileTracker(jax.jit(lambda x: x + 1), name="t",
                               registry=reg)
    x = jnp.ones((8,))
    f(x)
    f(x)
    assert (f.compiles, f.recompiles) == (1, 0)
    f(jax.device_put(x, NamedSharding(mesh8, P("clients"))))
    assert (f.compiles, f.recompiles) == (2, 1)
    snap = reg.snapshot()
    assert snap["telemetry.recompile_total{fn=t,reason=placement}"] == 1
    # A plain callable has no executable cache to consult.
    g = runtime.CompileTracker(lambda x: x, name="g", registry=reg)
    g(x), g(x)
    assert g.compiles == 1


def test_compile_tracker_forwards_calls_and_attrs():
    f = runtime.CompileTracker(jax.jit(lambda x: x + 1), name="t",
                               registry=fresh_registry())
    assert float(f(jnp.asarray(2.0))) == 3.0
    # AOT surface passes through: the perf script calls .lower() on it.
    assert hasattr(f, "lower")
    assert f.lower(jnp.asarray(2.0)) is not None


def test_cost_analysis_cached_per_signature():
    f = runtime.CompileTracker(jax.jit(lambda x: x @ x), name="t",
                               registry=fresh_registry())
    x = jnp.ones((16, 16))
    first = f.cost_analysis(x)
    again = f.cost_analysis(x)
    assert first["compile_s"] == again["compile_s"]   # cache hit: same dict
    if "flops" in first:                # CPU backend reports flops
        assert first["flops"] == pytest.approx(2 * 16 ** 3, rel=0.5)


def test_compiled_cost_handles_unjitted_functions():
    assert runtime.compiled_cost(lambda x: x, 1) == {}
    cost = runtime.compiled_cost(jax.jit(lambda x: x * x), jnp.ones((8,)))
    assert cost["compile_s"] > 0.0


def test_sample_device_memory_is_safe_on_cpu():
    reg = fresh_registry()
    stats = runtime.sample_device_memory(registry=reg)
    assert isinstance(stats, dict)     # CPU: {}; TPU: live gauges set
    if stats.get("bytes_in_use"):
        assert reg.gauge("runtime.hbm_bytes_in_use").value > 0


# ----------------------------------------------- Prometheus exposition ---
# Strict oracle for the text exposition 0.0.4 sample/comment grammar.
_PROM_LINE = re.compile(
    r"^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|summary)"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\"(,[a-zA-Z_][a-zA-Z0-9_]*"
    r"=\"[^\"]*\")*\})?"
    r" -?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?)$")


def populated_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("comm.retry_total").inc(3)
    reg.counter("telemetry.recompile_total",
                labels={"fn": "engine.round", "reason": "shape"}).inc()
    reg.gauge("runtime.hbm_bytes_in_use").set(2.5 * 2**30)
    reg.gauge("runtime.hbm_bytes_limit")          # never set: excluded
    reg.histogram("fed.round_time_s").observe(0.25)
    reg.histogram("fed.round_time_s").observe(0.75)
    return reg


def test_prometheus_text_matches_exposition_grammar():
    text = runtime.prometheus_text(populated_registry().typed_snapshot())
    assert text.endswith("\n")
    for line in text.splitlines():
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"


def test_prometheus_text_families_and_values():
    text = runtime.prometheus_text(populated_registry().typed_snapshot())
    assert "# TYPE colearn_comm_retry_total counter" in text
    assert "colearn_comm_retry_total 3" in text
    # Labeled child rendered with quoted labels under the parent family.
    assert ('colearn_telemetry_recompile_total'
            '{fn="engine.round",reason="shape"} 1') in text
    # TYPE emitted once per family even with labeled children present.
    assert text.count("# TYPE colearn_telemetry_recompile_total") == 1
    # Histogram -> summary with quantiles + count/sum.
    assert "# TYPE colearn_fed_round_time_s summary" in text
    assert 'colearn_fed_round_time_s{quantile="0.5"}' in text
    assert "colearn_fed_round_time_s_count 2" in text
    assert "colearn_fed_round_time_s_sum 1" in text
    # A gauge that was never set stays out of the exposition.
    assert "colearn_runtime_hbm_bytes_limit" not in text
    assert "colearn_runtime_hbm_bytes_in_use" in text


def test_prometheus_label_values_are_escaped():
    reg = MetricsRegistry()
    reg.counter("telemetry.compile_total",
                labels={"fn": 'we"ird\\name'}).inc()
    text = runtime.prometheus_text(reg.typed_snapshot())
    assert '{fn="we\\"ird\\\\name"}' in text


# ------------------------------------------------------------ exporter ---
def test_metrics_exporter_serves_both_endpoints():
    reg = populated_registry()
    with runtime.MetricsExporter(port=0, registry=reg) as exp:
        assert exp.port                # ephemeral port bound and readable
        base = f"http://127.0.0.1:{exp.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            assert "version=0.0.4" in r.headers["Content-Type"]
            text = r.read().decode("utf-8")
        assert "colearn_comm_retry_total 3" in text
        with urllib.request.urlopen(f"{base}/snapshot.json",
                                    timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["comm.retry_total"] == 3
        # Scrapes count themselves (visible on the NEXT scrape).
        assert reg.counter("export.scrapes_total").value == 2
    assert exp.port is None            # closed


def test_metrics_exporter_404_off_path():
    with runtime.MetricsExporter(port=0,
                                 registry=MetricsRegistry()) as exp:
        with pytest.raises(urllib.request.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/nope", timeout=10)


# ------------------------------------------------------------ EventLog ---
def test_event_log_appends_flushed_jsonl(tmp_path):
    path = tmp_path / "events" / "stream.jsonl"
    log = runtime.EventLog(str(path))
    log.emit("start", role="coordinator")
    log.emit("round", round=1, train_loss=0.5)
    # Flushed per line: readable BEFORE close (tail -f contract).
    docs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [d["event"] for d in docs] == ["start", "round"]
    assert all("ts" in d for d in docs)
    assert docs[1]["round"] == 1
    log.close()
    log.emit("after_close")            # silently dropped, no crash
    assert len(path.read_text().splitlines()) == 2


# ---------------------------------------------------------- colearn top --
def test_render_top_shows_counters_and_rates():
    snap = {"fed.rounds_total": 10, "fed.clients_dropped": 2,
            "comm.retry_total": 7, "telemetry.compile_total": 3,
            "telemetry.recompile_total": 1,
            "fed.round_time_s": {"count": 10, "p50": 0.5, "p90": 0.9,
                                 "max": 1.2},
            "runtime.hbm_bytes_in_use": 2 * 2**30,
            "runtime.hbm_bytes_limit": 8 * 2**30}
    prev = {"fed.rounds_total": 6}
    body = runtime.render_top(snap, prev=prev, interval_s=2.0)
    assert "rounds total" in body and "10" in body
    assert "(2.000/s)" in body         # (10-6)/2s
    assert "p50 0.500s" in body
    assert "recompiles 1" in body
    assert "(25.0%)" in body           # 2G of 8G
    # Pure function: renders from an empty snapshot without crashing.
    assert "colearn top" in runtime.render_top({})


# ------------------------------------------------- labeled instruments --
def test_labeled_histogram_child_rolls_up_and_exposes():
    reg = MetricsRegistry()
    reg.histogram("fed.phase_time_s",
                  labels={"phase": "agg_fold"}).observe(0.2)
    reg.histogram("fed.phase_time_s",
                  labels={"phase": "downlink"}).observe(0.4)
    # every child observation also lands in the unlabeled aggregate, so
    # render_top latency lines and family-level SLO gates keep working
    assert reg.histogram("fed.phase_time_s").count == 2
    assert reg.histogram(
        "fed.phase_time_s", labels={"phase": "agg_fold"}).count == 1

    text = runtime.prometheus_text(reg.typed_snapshot())
    for line in text.splitlines():
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    # one family: TYPE once, children keyed by merged label sets
    assert text.count("# TYPE colearn_fed_phase_time_s summary") == 1
    assert ('colearn_fed_phase_time_s'
            '{quantile="0.5",phase="agg_fold"} 0.2') in text
    assert 'colearn_fed_phase_time_s_count{phase="agg_fold"} 1' in text
    assert 'colearn_fed_phase_time_s_sum{phase="downlink"} 0.4' in text
    assert "colearn_fed_phase_time_s_count 2" in text  # the aggregate


def test_labeled_gauge_child_does_not_roll_up():
    reg = MetricsRegistry()
    reg.gauge("health.device_score", labels={"device": "2"}).set(11)
    snap = reg.snapshot()
    assert snap["health.device_score{device=2}"] == 11.0
    # "last across labels" is noise: the parent gauge stays unset and
    # out of the exposition
    text = runtime.prometheus_text(reg.typed_snapshot())
    assert 'colearn_health_device_score{device="2"} 11' in text
    assert "\ncolearn_health_device_score 1" not in text


def test_render_top_aggregator_tier_section():
    snap = {"fed.rounds_total": 4,
            "comm.agg_heartbeat_age_s{agg=0}": 0.8,
            "comm.agg_heartbeat_age_s{agg=1}": 12.5,
            "comm.agg_slice_devices{agg=0}": 3,
            "comm.agg_slice_devices{agg=1}": 2,
            "comm.agg_partials_folded_total{agg=0}": 12,
            "comm.agg_failovers_total": 1}
    body = runtime.render_top(snap)
    assert "aggregator tier" in body
    agg0 = next(ln for ln in body.splitlines() if "agg 0" in ln)
    assert "hb age" in agg0 and "0.80s" in agg0
    assert "slice    3" in agg0 and "partials     12" in agg0
    assert "failovers" in body
    # flat runs keep the old layout: no tier section at all
    assert "aggregator tier" not in runtime.render_top(
        {"fed.rounds_total": 4})


# ------------------------------------------- staleness observatory ------
def test_staleness_histogram_and_arrival_gauge_exposition():
    # The async coordinator's observatory instruments, as scraped: the
    # outcome-labeled staleness histogram must expose as ONE summary
    # family with per-outcome children plus the unlabeled roll-up, and
    # the arrival estimator's gauges must land fleet + per-device.
    from colearn_federated_learning_tpu.telemetry.arrival import (
        ArrivalEstimator,
    )
    reg = MetricsRegistry()
    for tau in (0, 1, 3):
        reg.histogram("async.staleness",
                      labels={"outcome": "folded"}).observe(tau)
    reg.histogram("async.staleness",
                  labels={"outcome": "discarded"}).observe(9)
    est = ArrivalEstimator()
    est.observe("d0", now=0.0)
    est.observe("d0", now=2.0)
    est.export_gauges(reg, "async.arrival_rate_per_s")

    text = runtime.prometheus_text(reg.typed_snapshot())
    for line in text.splitlines():
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    assert text.count("# TYPE colearn_async_staleness summary") == 1
    assert ('colearn_async_staleness'
            '{quantile="0.5",outcome="folded"} 1') in text
    assert 'colearn_async_staleness_count{outcome="folded"} 3' in text
    assert 'colearn_async_staleness_sum{outcome="discarded"} 9' in text
    assert "colearn_async_staleness_count 4" in text    # the roll-up
    assert "# TYPE colearn_async_arrival_rate_per_s gauge" in text
    assert "colearn_async_arrival_rate_per_s 0.5" in text
    assert 'colearn_async_arrival_rate_per_s{device="d0"} 0.5' in text


def test_render_top_async_plane_section():
    snap = {"fed.rounds_total": 4,
            "async.aggregations_total": 12,
            "async.buffer_target": 8,
            "async.arrival_rate_per_s": 2.5,
            "async.updates_discarded_stale": 3,
            "async.staleness": {"count": 15, "sum": 20.0,
                                "p50": 1.0, "p90": 4.0, "p99": 6.0},
            "async.contribution_mass{outcome=folded}": 10.5,
            "async.contribution_mass{outcome=discarded}": 0.75,
            "async.pumps{state=wait}": 5,
            "async.pumps{state=train}": 3}
    body = runtime.render_top(snap)
    assert "async plane" in body
    assert "aggregations" in body and "12" in body
    assert "buffer K" in body
    assert "arrival rate" in body and "2.500/s" in body
    assert "stale discards" in body
    stale = next(ln for ln in body.splitlines() if "staleness" in ln)
    assert "p50 1.0" in stale and "p90 4.0" in stale and "p99 6.0" in stale
    mass = next(ln for ln in body.splitlines() if "mass folded" in ln)
    assert "10.50" in mass and "0.75" in mass
    pumps = next(ln for ln in body.splitlines() if "pumps" in ln)
    assert "wait 5" in pumps and "train 3" in pumps
    # flat sync snapshots keep the classic layout: no async section
    assert "async plane" not in runtime.render_top(
        {"fed.rounds_total": 4})


def test_render_top_async_plane_fleetsim_aliases():
    # fleetsim's virtual-clock async plane feeds the same section
    # through its own metric names (per-minute rate units).
    snap = {"fleetsim.async_aggregations_total": 6,
            "fleetsim.async_buffer_size": 4,
            "fleetsim.async_arrival_rate_per_min": 1.2,
            "fleetsim.async_updates_discarded_total": 2,
            "fleetsim.async_staleness": {"count": 8, "sum": 9.0,
                                         "p50": 1.0, "p90": 2.0,
                                         "p99": 3.0}}
    body = runtime.render_top(snap)
    assert "async plane" in body
    assert "1.200/min" in body
    assert "p99 3.0" in body
