"""The reduction from a trace to numbers: on hand-made events, and pinned
on a trace recorded on the chip (``data/``, trimmed; see its README)."""

import os
import types

import pytest

from benchmarks.harness import xplane
from benchmarks.harness.xplane import Device, Trace
from benchmarks.layer_metrics import (
    allreduce_ms_per_round,
    device_idle_share,
    round_device_ms,
)

US = 1e3   # the events below are written in microseconds


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


def hand_made() -> Trace:
    """One chip, two executions of a round program and one evaluation
    inside one ``fit`` span of 100 us; the device idles 10 + 10 + 10 + 30."""
    ops = [
        ev("while.1", 10, 30), ev("fusion.1", 10, 10), ev("fusion.2", 20, 20),
        ev("all-reduce.1", 50, 5), ev("fusion.1", 55, 5),
        ev("fusion.9", 70, 0),
    ]
    modules = [ev("jit_round_fn(1)", 10, 30), ev("jit_round_fn(1)", 50, 10),
               ev("jit_eval_fn(2)", 70, 0)]
    return Trace(devices={0: Device(ops=ops, modules=modules)},
                 spans=[ev("fit", 0, 100)])


def reading(trace, rounds):
    return types.SimpleNamespace(trace=trace, rounds=rounds)


def test_busy_is_the_union_not_the_sum():
    trace = hand_made()
    assert xplane.busy_s(trace.devices[0].ops) == pytest.approx(40e-6)
    assert trace.window_s == pytest.approx(100e-6)
    assert device_idle_share.read(reading(trace, 2)) == pytest.approx(60.0)


def test_self_time_takes_children_from_their_container():
    times = xplane.self_times(hand_made().devices[0].ops)
    assert times["while.1"] == pytest.approx(0.0)
    assert times["fusion.1"] == pytest.approx(15e-6)
    assert times["fusion.2"] == pytest.approx(20e-6)
    assert sum(times.values()) == pytest.approx(40e-6)
    assert xplane.top(times, n=2) == [["fusion.2", pytest.approx(20e-6)],
                                      ["fusion.1", pytest.approx(15e-6)]]


def test_round_time_leaves_the_evaluation_out():
    trace = hand_made()
    trace.devices[0].modules[2] = ev("jit_eval_fn(2)", 70, 25)
    assert round_device_ms.read(reading(trace, 2)) == pytest.approx(20e-3)
    assert allreduce_ms_per_round.read(reading(trace, 2)) == pytest.approx(
        2.5e-3)


def test_idle_gaps_say_what_was_going_on():
    trace = hand_made()
    trace.devices[0].ops[-1] = ev("fusion.9", 70, 2)
    trace.devices[0].modules[2] = ev("jit_eval_fn(2)", 70, 2)
    trace.spans = [ev("fit", 5, 40), ev("fit", 62, 26)]     # window 5..88
    # A gap belongs to what was going on when it began.
    assert xplane.idle_gaps(trace, 0) == {
        "fit:before any program": pytest.approx(5e-6),            # 5..10
        "fit:after jit_round_fn": pytest.approx(10e-6),           # 40..50
        "between_chunks:after jit_round_fn": pytest.approx(10e-6),  # 60..70
        "fit:after jit_eval_fn": pytest.approx(16e-6),            # 72..88
    }
    # A wait between two operations of one running program.
    trace.devices[0].modules[0] = ev("jit_round_fn(1)", 10, 45)
    gaps = xplane.idle_gaps(trace, 0)
    assert gaps["fit:inside jit_round_fn"] == pytest.approx(10e-6)
    assert "fit:after jit_round_fn" not in gaps


def test_events_outside_the_window_do_not_count():
    trace = hand_made()
    trace.spans = [ev("fit", 15, 10)]            # 15..25 us
    clipped = xplane.clip(trace.devices[0].ops, trace.window_ns)
    assert xplane.busy_s(clipped) == pytest.approx(10e-6)
    assert device_idle_share.read(reading(trace, 1)) == pytest.approx(0.0)


def test_readers_return_nothing_without_a_trace():
    for reader in (round_device_ms, allreduce_ms_per_round,
                   device_idle_share):
        assert reader.read(reading(None, 3)) is None
        assert reader.read(reading(Trace(devices={}, spans=[]), 3)) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "cnn_mesh4_v5e_trimmed.xplane.pb")


def test_recorded_trace_from_the_chip():
    """One round and one evaluation of ``cnn_mesh4`` on two of its four
    chips (``data/README.md``)."""
    trace = xplane.load(RECORDED, ["fit"])
    assert sorted(trace.devices) == [0, 1]
    assert [xplane.module_name(m[0]) for m in trace.devices[0].modules] == [
        "jit_body", "jit_eval_fn"]
    assert trace.window_s == pytest.approx(0.567110823)
    one_round = reading(trace, 1)
    assert device_idle_share.read(one_round) == pytest.approx(0.58338033)
    assert round_device_ms.read(one_round) == pytest.approx(534.170095)
    assert allreduce_ms_per_round.read(one_round) == pytest.approx(0.083417)
    ops = xplane.clip(trace.devices[0].ops, trace.window_ns)
    assert xplane.busy_s(ops) == pytest.approx(0.563803261)
    # Self times add up to the busy time: nothing is counted twice.
    assert sum(xplane.self_times(ops).values()) == pytest.approx(
        0.563803261, rel=1e-6)
    assert xplane.top(xplane.self_times(ops), n=1)[0][0].startswith(
        "convert_reduce_fusion.23 ")
    gaps = xplane.idle_gaps(trace, 0)
    assert max(gaps, key=gaps.get) == "fit:inside jit_body"
    assert sum(gaps.values()) == pytest.approx(0.567110823 - 0.563803261)


def test_op_label():
    text = ("%fusion.12 = (f32[64]{0:T(128)}, bf16[128,32,32,64]{3,0,2,1}) "
            "fusion(f32[3,3,64,64]{3,2,1,0} %copy.1, pred[] %p), kind=kLoop")
    assert xplane.op_label(text) == "fusion.12 bf16[128,32,32,64]"
    assert xplane.op_label("fit") == "fit"
    assert xplane.op_label("%all-reduce.3 = f32[10]{0} all-reduce(...)") == (
        "all-reduce.3 f32[10]")
