"""The by-scope readers (``layer_metrics/_scopes.py`` and the readers on
it): on a hand-made trace and hand-made scope tables; what they give a
program that offers no table; their entries in ``BENCHMARK.json``, found by
name, with every older entry as it was."""

import hashlib
import json
import types

import pytest

import tiny
from benchmarks.harness.spec import Bench
from benchmarks.harness.xplane import Device, Trace
from benchmarks.layer_metrics import _program, _scopes
from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.telemetry import Scope

ALL = ["cnn_device_bound", "cnn_mesh4", "bert_memory_bound",
       "bert_eval_every_round", "evabyte_seq16k", "nemotron_hybrid_seq16k",
       "xing_mla_mhc_seq8k"]
ENTRIES = {                 # name: (layer, source, unit, better, cells)
    "scope_matched_share": ("device", "device_trace", "%", "higher", ALL),
    "scope_named_share": ("device", "device_trace", "%", "higher", ALL),
    "scope_table_s": ("orchestration", "program_counter", "s", "lower", ALL),
    "cohort_ms_per_round": ("round_program", "device_trace", "ms", "lower",
                            ALL),
    "local_optimizer_ms_per_round": ("round_program", "device_trace", "ms",
                                     "lower", ALL),
    "local_other_ms_per_round": ("round_program", "device_trace", "ms",
                                 "lower", ALL),
    "aggregate_ms_per_round": ("round_program", "device_trace", "ms",
                               "lower", ALL),
    "server_update_ms_per_round": ("round_program", "device_trace", "ms",
                                   "lower", ALL),
    "model_forward_ms_per_round": ("models", "device_trace", "ms", "lower",
                                   ALL),
    "model_backward_ms_per_round": ("models", "device_trace", "ms", "lower",
                                    ALL),
    "model_remat_ms_per_round": ("models", "device_trace", "ms", "lower",
                                 ALL[4:]),
    "moe_ms_per_round": ("models", "device_trace", "ms", "lower", ALL[5:]),
    "moe_route_ms_per_round": ("models", "device_trace", "ms", "lower",
                               ALL[5:]),
    "moe_tiles_ms_per_round": ("models", "device_trace", "ms", "lower",
                               ALL[5:]),
    "ssd_scope_ms_per_round": ("kernels", "device_trace", "ms", "lower",
                               ALL[5:6]),
    "mla_ms_per_round": ("models", "device_trace", "ms", "lower", ALL[6:]),
    "mhc_scope_ms_per_round": ("models", "device_trace", "ms", "lower",
                               ALL[6:]),
    "head_ms_per_round": ("models", "device_trace", "ms", "lower", ALL[4:]),
}
# All of them read the device plane of a trace (``scope_table_s``: asks for
# no table without one).  ``test_cells_cpu.py`` takes the set from ``tiny``
# when its tests run, after every test module has been imported.
tiny.NOT_ON_CPU |= set(ENTRIES)

BENCH = Bench(tiny.REPO)
# ``json.dumps(per_layer[:37], sort_keys=True)`` of the parent's file.
OLDER = 37, "229a2011b6aea07691fa7f156e5b58106844f64ce37efd6d3fb058086f1c5066"
US = 1e3


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


def hand_made():
    """One chip, one ``fit`` span of 200 us: two executions of the round
    program and one of the evaluation's.  Both programs have a
    ``fusion.1``, which means another thing in each; the round's ``while.1``
    holds two children and 2 us of its own."""
    ops = [
        # round 1, 10..60
        ev("fusion.1 f32[8]", 10, 4),                  # cohort
        ev("while.1 (f32[8])", 14, 40),                # 2 us of its own
        ev("fusion.2 bf16[8,8]", 14, 10),              # forward, moe.route
        ev("fusion.3 bf16[8,8]", 24, 28),              # backward, moe.tiles
        ev("copy.4 f32[8]", 54, 6),                    # no op_name
        # round 2, 100..144
        ev("fusion.1 f32[8]", 100, 4),
        ev("fusion.5 f32[8]", 104, 16),                # remat, ssd
        ev("fusion.6 f32[8]", 120, 10),                # local.optimizer
        ev("fusion.7 f32[8]", 130, 6),                 # server
        ev("fusion.8 f32[8]", 136, 4),                 # local, no phase
        # the evaluation, 150..170
        ev("fusion.1 f32[8]", 150, 20),                # its own fusion.1
    ]
    modules = [ev("jit_round_fn(1)", 10, 50), ev("jit_round_fn(1)", 100, 44),
               ev("jit_eval_fn(2)", 150, 20)]
    trace = Trace(devices={0: Device(ops=ops, modules=modules)},
                  spans=[ev("fit", 0, 200)])
    local = ("local", "Model", "layer_0")
    tables = {
        "jit_round_fn": {
            "fusion.1": Scope("none", ("cohort",)),
            "while.1": Scope("none", ("local",)),
            "fusion.2": Scope("forward", (*local, "moe", "moe.route")),
            "fusion.3": Scope("backward", (*local, "moe", "moe.tiles")),
            "copy.4": Scope("none", ()),
            "fusion.5": Scope("remat", (*local, "mixer", "ssd")),
            "fusion.6": Scope("none", ("local", "local.optimizer")),
            "fusion.7": Scope("none", ("server",)),
            "fusion.8": Scope("none", ("local",)),
        },
        "jit_eval_fn": {"fusion.1": Scope("none", ("Model", "head"))},
    }
    return trace, tables


def reading(trace, rounds=2):
    return types.SimpleNamespace(trace=trace, rounds=rounds)


def read(name, r):
    return BENCH.module("layer_metrics", name).read(r)


@pytest.fixture
def offered(monkeypatch):
    """The program offers the hand-made tables."""
    trace, tables = hand_made()
    monkeypatch.setattr(_scopes, "tables", lambda: tables)
    return trace, tables


def test_operations_are_looked_up_in_the_program_they_ran_in(offered):
    trace, tables = offered
    parts = _scopes.split_trace(trace, tables)
    # The evaluation's fusion.1 (20 us) is not the round's (2 x 4 us).
    assert parts.round[Scope("none", ("cohort",))] == pytest.approx(8e-6)
    assert parts.round_s == pytest.approx(90e-6)
    assert parts.total_s == parts.matched_s == pytest.approx(110e-6)
    assert read("scope_matched_share", reading(trace)) == pytest.approx(100)


def test_a_while_counts_its_children_once(offered):
    trace, _ = offered
    r = reading(trace)
    # while.1: 40 us less its children's 38: 2 us under ``local`` with no
    # phase, beside fusion.8's 4.
    assert read("local_other_ms_per_round", r) == pytest.approx(3e-3)
    assert read("model_forward_ms_per_round", r) == pytest.approx(5e-3)
    assert read("model_backward_ms_per_round", r) == pytest.approx(14e-3)
    assert read("model_remat_ms_per_round", r) == pytest.approx(8e-3)


def test_the_two_cuts(offered):
    trace, _ = offered
    r = reading(trace)
    by_phase = {name: read(name, r) for name in (
        "cohort_ms_per_round", "local_optimizer_ms_per_round",
        "local_other_ms_per_round", "aggregate_ms_per_round",
        "server_update_ms_per_round", "model_forward_ms_per_round",
        "model_backward_ms_per_round", "model_remat_ms_per_round")}
    assert by_phase["cohort_ms_per_round"] == pytest.approx(4e-3)
    assert by_phase["local_optimizer_ms_per_round"] == pytest.approx(5e-3)
    assert by_phase["server_update_ms_per_round"] == pytest.approx(3e-3)
    assert by_phase["aggregate_ms_per_round"] == 0.0       # fused away
    named = read("scope_named_share", r)
    assert named == pytest.approx(100 * 84 / 90)           # copy.4: 6 us
    # By phase, every operation is in one part: with the unnamed, the
    # round program's busy time.
    assert sum(by_phase.values()) == pytest.approx(45e-3 * named / 100)
    # By part, across phases.
    assert read("moe_ms_per_round", r) == pytest.approx(19e-3)
    assert read("moe_route_ms_per_round", r) == pytest.approx(5e-3)
    assert read("moe_tiles_ms_per_round", r) == pytest.approx(14e-3)
    assert read("ssd_scope_ms_per_round", r) == pytest.approx(8e-3)
    assert read("mla_ms_per_round", r) == 0.0
    # The evaluation's head is not the round's.
    assert read("head_ms_per_round", r) == 0.0


def test_an_unmatched_instruction_silences_the_rest(offered):
    """2 us of 112 under a name the round's table lacks: 98.2% matched,
    under the floor, and no by-scope reader says anything."""
    trace, _ = offered
    trace.devices[0].ops.append(ev("fusion.99 f32[8]", 140, 2))
    r = reading(trace)
    assert read("scope_matched_share", r) == pytest.approx(100 * 110 / 112)
    for name in set(ENTRIES) - {"scope_matched_share", "scope_table_s"}:
        assert read(name, r) is None, name


def test_remat_is_none_without_a_rematerialised_block(offered):
    trace, tables = offered
    tables["jit_round_fn"]["fusion.5"] = Scope("forward", ("local", "ssd"))
    r = reading(trace)
    assert read("model_remat_ms_per_round", r) is None
    assert read("model_forward_ms_per_round", r) == pytest.approx(13e-3)


def test_a_program_without_tables_reads_none(monkeypatch):
    """The parent of the PR that added the readers has no
    ``program_scopes``; a run that was not traced, or has no device plane,
    asks for no table at all."""
    trace, _ = hand_made()
    monkeypatch.delattr(telemetry, "program_scopes")
    for name in ENTRIES:
        assert read(name, reading(trace)) is None, name
    monkeypatch.undo()
    monkeypatch.setattr(telemetry, "program_scopes", lambda: pytest.fail(
        "asked for a table"))
    for r in (reading(None), reading(Trace(devices={}, spans=[]))):
        for name in ENTRIES:
            assert read(name, r) is None, name


def test_a_table_that_cannot_be_built_leaves_the_metrics_out(monkeypatch,
                                                             capsys):
    trace, _ = hand_made()

    def fails():
        raise RuntimeError("compiler said no")

    monkeypatch.setattr(telemetry, "program_scopes", fails)
    assert read("cohort_ms_per_round", reading(trace)) is None
    assert "compiler said no" in capsys.readouterr().err


def test_scope_table_s_is_the_programs_counter(offered, monkeypatch):
    trace, _ = offered
    monkeypatch.setattr(_program, "counter", {
        "telemetry.scope_table_seconds": 16.5}.get)
    assert read("scope_table_s", reading(trace)) == 16.5
    monkeypatch.setattr(_program, "counter", {}.get)
    assert read("scope_table_s", reading(trace)) is None


def test_the_entries_are_found_by_name_and_the_older_ones_unchanged():
    per_layer = BENCH.doc["per_layer"]
    count, digest = OLDER
    assert hashlib.sha256(json.dumps(
        per_layer[:count], sort_keys=True).encode()).hexdigest() == digest
    layers = {m["layer"] for m in per_layer[:count]}
    by_name = {m["name"]: m for m in per_layer}
    assert len(by_name) == len(per_layer)
    assert set(ENTRIES) == {m["name"] for m in per_layer[count:]}
    for name, (layer, source, unit, better, cells) in ENTRIES.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "client_samples_per_s_per_chip",
            "workloads": cells}, name
        assert layer in layers
        assert callable(BENCH.module("layer_metrics", name).read)
    for cell in BENCH.doc["workloads"]:
        have = {m["name"] for m in BENCH.metrics("per_layer", cell["name"])}
        assert have & set(ENTRIES) == {
            name for name, entry in ENTRIES.items()
            if cell["name"] in entry[4]}
