"""The readers of the program's own spans and counters
(``layer_metrics/_program.py`` and the eight beside it): on hand-made
events, on the trace recorded on the chip, and end to end on the CPU."""

import os
import types

import pytest

import tiny
from benchmarks.harness import xplane
from benchmarks.harness.spec import Bench
from benchmarks.harness.xplane import Device, Trace
from benchmarks.layer_metrics import (
    _program,
    host_gap_bookkeeping_ms,
    host_gap_enqueue_ms,
    host_gap_ms_per_round,
    host_gap_sync_ms,
)
from colearn_federated_learning_tpu import telemetry

US = 1e3   # the events below are written in microseconds
GAP_READERS = (host_gap_ms_per_round, host_gap_sync_ms,
               host_gap_bookkeeping_ms, host_gap_enqueue_ms)
SPAN_READERS = GAP_READERS[1:]


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


def hand_made() -> Trace:
    """One chip, one round program and one evaluation inside one ``fit``
    of the harness (0..100 us) and, nested in it, the program's (1..99).
    The device idles 10 (before the round), 2 (between two operations of
    the round program), 20 (from 1 us before the round program's module
    ends to the evaluation's first operation) and 30 (after the
    evaluation's last operation, 5 us before its module ends)."""
    ops = [ev("fusion.1", 10, 10), ev("fusion.2", 22, 18),
           ev("fusion.9", 60, 10)]
    modules = [ev("jit_round_fn(1)", 10, 31), ev("jit_eval_fn(2)", 58, 17)]
    return Trace(devices={0: Device(ops=ops, modules=modules)},
                 spans=[ev("fit", 0, 100), ev("fit", 1, 98)])


# The program's spans as its tracer holds them: on a clock of its own,
# 5 ms ahead of the trace's and running a thousandth fast.
PROGRAM = [("fit", 1, 99, None), ("round", 2, 98, "fit"),
           ("enqueue", 3, 12, "round"), ("sync_metrics", 12, 45, "round"),
           ("bookkeeping", 45, 50, "round"), ("bookkeeping", 50, 52, "round"),
           ("evaluate", 52, 90, "round"), ("log", 90, 95, "round")]
EXPECTED_US = {"enqueue": 7, "sync_metrics": 5, "bookkeeping": 7, "log": 5,
               "evaluate": 28, "round": 4, "fit": 2, _program.NO_SPAN: 2}


def own_clock(t_us: float) -> int:
    return round((5000 + t_us * 1.001) * US)


def record(spans=PROGRAM, trace_id="t0"):
    telemetry.get_tracer().adopt([
        {"name": name, "trace_id": trace_id, "span_id": f"{trace_id}.{i}",
         "parent_id": parent and f"{trace_id}.{[s[0] for s in spans].index(parent)}",
         "start_ns": own_clock(start), "end_ns": own_clock(end)}
        for i, (name, start, end, parent) in enumerate(spans)])


@pytest.fixture(autouse=True)
def empty_buffer():
    telemetry.get_tracer().clear()
    yield
    telemetry.get_tracer().clear()


def reading(trace, rounds):
    return types.SimpleNamespace(trace=trace, rounds=rounds)


def test_host_gap_is_idle_not_wholly_inside_one_execution():
    trace = hand_made()
    gaps, inside_s = _program.host_gaps(trace, 0)
    assert gaps == [(0, 10 * US), (40 * US, 60 * US), (70 * US, 100 * US)]
    assert inside_s == pytest.approx(2e-6)
    # The identity the split rests on.
    busy_s = xplane.busy_s(xplane.clip(trace.devices[0].ops, trace.window_ns))
    assert sum(e - s for s, e in gaps) / 1e9 + inside_s == pytest.approx(
        trace.window_s - busy_s)
    assert host_gap_ms_per_round.read(reading(trace, 2)) == pytest.approx(
        30e-3)
    # xplane.idle_gaps files the 20 us under "inside": the gap begins in
    # the module's last microsecond.
    assert xplane.idle_gaps(trace, 0)["fit:inside jit_round_fn"] == (
        pytest.approx(22e-6))


def test_each_instant_goes_to_the_innermost_span_open():
    trace = hand_made()
    record()
    placed, error_ns = _program.placed_spans(trace)
    assert len(placed) == len(PROGRAM)
    assert error_ns == pytest.approx(98 * US * 1e-3, abs=1)   # the drift
    for (name, start, end), (want, a, b, _) in zip(placed, PROGRAM):
        assert name == want
        assert (start, end) == pytest.approx((a * US, b * US), abs=1)
    parts = _program.host_gap_split(reading(trace, 2))
    assert parts == {name: pytest.approx(us * 1e-6, abs=2e-9)
                     for name, us in EXPECTED_US.items()}
    # The three parts and the remainder add up to the whole.
    assert sum(parts.values()) * 1e3 / 2 == pytest.approx(
        host_gap_ms_per_round.read(reading(trace, 2)))
    assert host_gap_sync_ms.read(reading(trace, 2)) == pytest.approx(
        2.5e-3, abs=1e-6)
    assert host_gap_bookkeeping_ms.read(reading(trace, 2)) == pytest.approx(
        6e-3, abs=1e-6)
    assert host_gap_enqueue_ms.read(reading(trace, 2)) == pytest.approx(
        3.5e-3, abs=1e-6)


def test_two_chunks_are_placed_each_by_its_own_fit():
    trace = hand_made()
    later = 200
    trace.spans += [ev("fit", later, 100), ev("fit", later + 1, 98)]
    trace.devices[0].ops += [(n, s + later * US, d)
                             for n, s, d in trace.devices[0].ops]
    trace.devices[0].modules += [(n, s + later * US, d)
                                 for n, s, d in trace.devices[0].modules]
    record()
    record([(n, a + later + 40, b + later + 40, p)   # its clock jumped
            for n, a, b, p in PROGRAM], trace_id="t1")
    parts = _program.host_gap_split(reading(trace, 4))
    between = 100e-6                         # 100..200 us: no fit at all
    assert parts.pop(_program.NO_SPAN) == pytest.approx(
        2 * 2e-6 + between, abs=4e-9)
    assert parts == {name: pytest.approx(2 * us * 1e-6, abs=4e-9)
                     for name, us in EXPECTED_US.items()
                     if name != _program.NO_SPAN}


def test_spans_that_cannot_be_placed_give_nothing():
    trace = hand_made()
    # A program that records no spans (the parent of this PR).
    for reader in SPAN_READERS:
        assert reader.read(reading(trace, 2)) is None
    assert host_gap_ms_per_round.read(reading(trace, 2)) is not None
    # Another count of fit spans than of innermost fit events.
    record()
    record(trace_id="t1")
    assert _program.placed_spans(trace) is None
    # Durations that disagree by more than the bound.
    telemetry.get_tracer().clear()
    record([("fit", 1, 300, None)] + PROGRAM[1:])
    assert _program.placed_spans(trace) is None
    for reader in SPAN_READERS:
        assert reader.read(reading(trace, 2)) is None


def test_gap_readers_return_nothing_without_a_device_trace():
    record()
    for reader in GAP_READERS:
        assert reader.read(reading(None, 3)) is None
        assert reader.read(reading(Trace(devices={}, spans=[]), 3)) is None
        assert reader.read(reading(hand_made(), 0)) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "cnn_mesh4_v5e_trimmed.xplane.pb")


def test_recorded_trace_from_the_chip():
    """The round program's last operation ends 1.7 us before its module
    does, and the evaluation's first operation starts 2.660 ms later:
    host time, which ``xplane.idle_gaps`` files under ``inside jit_body``
    (``test_xplane.py``)."""
    trace = xplane.load(RECORDED, ["fit"])
    gaps, inside_s = _program.host_gaps(trace, 0)
    assert [(e - s) / 1e6 for s, e in gaps] == pytest.approx(
        [0.336621, 2.660301, 0.301117])      # the first and last: the cut
    assert (5542302104.0, 5544962405.0) in gaps
    assert inside_s == pytest.approx(9.523e-6)
    assert sum(e - s for s, e in gaps) / 1e9 + inside_s == pytest.approx(
        0.567110823 - 0.563803261)
    assert host_gap_ms_per_round.read(reading(trace, 1)) == pytest.approx(
        3.298039)
    # No program spans came with that trace.
    assert host_gap_sync_ms.read(reading(trace, 1)) is None


COUNTER_METRICS = {"round_program_ready_s", "eval_program_ready_s",
                   "setup_cache_misses", "learner_build_s"}
NOT_ON_CPU = {"round_device_ms", "device_idle_share", "hbm_peak_reserved_gb",
              "host_gap_ms_per_round", "host_gap_sync_ms",
              "host_gap_bookkeeping_ms", "host_gap_enqueue_ms"}


def test_traced_run_reports_the_programs_counters(tmp_path):
    """The counters come through a whole run on the CPU; the gaps need a
    device plane.  A second run of the same seed finds every program of
    the learner in the cache."""
    root = tiny.make_root(str(tmp_path))
    names = {m["name"]
             for m in Bench(root).metrics("per_layer", "cnn_device_bound")}
    assert COUNTER_METRICS | NOT_ON_CPU <= names
    for attempt in range(2):
        process = tiny.run(root, "cnn_device_bound", 1, seed=11, seconds=0.5,
                           trace=1)
        assert process.returncode == 0, process.stderr[-2000:]
        metrics = tiny.lines(process)[-1]["metrics"]
        assert set(metrics) == names - NOT_ON_CPU
        for name in COUNTER_METRICS - {"setup_cache_misses"}:
            assert metrics[name]["value"] > 0 and metrics[name]["unit"] == "s"
    assert metrics["setup_cache_misses"] == {"value": 0.0, "unit": "programs"}
