"""``moe_row_tiles_visited_share``: its arithmetic on hand-made trace
events, and its entry in ``BENCHMARK.json``, added as a file and an entry
with nothing that was there edited."""

import types

import pytest

import tiny
from benchmarks.harness.spec import Bench
from benchmarks.harness.xplane import Device, Trace
from benchmarks.layer_metrics import _program, moe_row_tiles_visited_share

# It reads the device plane of a trace (``tiny.NOT_ON_CPU``).
tiny.NOT_ON_CPU |= {"moe_row_tiles_visited_share"}

NAME = "moe_row_tiles_visited_share"
CELL = "nemotron_hybrid_seq16k"
US = 1e3
# 4 tiles a layer and step, 2 layers, remat: 4 x (2 x 2 + 6) x 2 = 80 calls
# a step if every tile is visited.
GAUGES = {"moe.row_tile": 8.0, "moe.dispatch_rows": 32.0,
          "hybrid.layers{kind=moe}": 2.0}


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


def reading(kernels_a_round, rounds=2, remat=True, local_steps=2):
    """``rounds`` executions of a round program of 100 us each with
    ``kernels_a_round`` grouped-product calls in it, an evaluation with
    calls of its own after them, and a call before the window opens."""
    ops = [ev("ragged-dot-none.7 bf16[8,16]", 1, 1)]
    modules = [ev("jit_round_fn(1)", 0, 4)]
    for i in range(rounds):
        at = 10 + 100 * i
        modules.append(ev("jit_round_fn(1)", at, 100))
        ops.append(ev("while.3", at, 90))
        ops.append(ev("ragged-dot-metadata.1 s32[71]", at, 1))
        ops += [ev(f"ragged-dot-none.{k % 8} bf16[8,16]", at + 1 + k / 4, 0.2)
                for k in range(kernels_a_round)]
        ops.append(ev("fusion.2 bf16[8,16]", at + 95, 1))
    at = 10 + 100 * rounds
    modules.append(ev("jit_eval_fn(2)", at, 50))
    ops += [ev("ragged-dot-none.1 bf16[8,16]", at + k, 0.5) for k in range(9)]
    trace = Trace(devices={0: Device(ops=ops, modules=modules)},
                  spans=[ev("fit", 5, at + 60)])
    return types.SimpleNamespace(
        trace=trace, rounds=rounds, chips=1, samples_per_round=local_steps,
        traffic={"batch": 1, "local_steps": local_steps},
        config={"experiment": {"fed": {"batch_size": 4},
                               "model": {"remat": remat}}})


@pytest.fixture
def gauges(monkeypatch):
    have = dict(GAUGES)
    monkeypatch.setattr(_program, "counter", have.get)
    return have


@pytest.mark.parametrize("kernels,remat,want", [
    (160, True, 100.0),          # every tile of both steps of a round
    (20, True, 12.5),            # one tile in eight
    (128, False, 100.0),         # 4 x (2 + 6) x 2 layers x 2 steps
    (16, False, 12.5),
])
def test_share_is_calls_seen_over_calls_at_the_bound(gauges, kernels, remat,
                                                     want):
    got = moe_row_tiles_visited_share.read(reading(kernels, remat=remat))
    assert got == pytest.approx(want)


def test_the_evaluation_and_the_metadata_are_not_counted(gauges):
    r = reading(20)
    assert moe_row_tiles_visited_share.visits_in_round_programs(
        r.trace) == 40
    assert not moe_row_tiles_visited_share.is_visit(
        "ragged-dot-metadata.19 s32[71]")
    assert moe_row_tiles_visited_share.is_visit(
        "ragged-dot-none.49 bf16[2048,2688]")


def test_a_tile_that_does_not_divide_the_rows_rounds_up(gauges):
    gauges["moe.row_tile"] = 12.0          # 3 tiles of 12 hold 32 rows
    assert moe_row_tiles_visited_share.read(reading(30)) == pytest.approx(
        100.0 * 60 / (3 * 10 * 2 * 4))


@pytest.mark.parametrize("missing", sorted(GAUGES))
def test_a_program_without_the_gauges_reads_none(gauges, missing):
    """The parent of the PR that added the reader sets no ``moe.row_tile``;
    another family sets none of them."""
    del gauges[missing]
    assert moe_row_tiles_visited_share.read(reading(20)) is None


def test_nothing_to_read_is_none(gauges):
    untraced = reading(20)
    untraced.trace = None
    assert moe_row_tiles_visited_share.read(untraced) is None
    assert moe_row_tiles_visited_share.read(reading(0)) is None
    no_device = reading(20)
    no_device.trace = Trace(devices={}, spans=no_device.trace.spans)
    assert moe_row_tiles_visited_share.read(no_device) is None


def test_the_entry_is_the_cells_alone():
    bench = Bench(tiny.REPO)
    entry = bench._entry("per_layer", NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "models",
        "moves": "client_samples_per_s_per_chip", "workloads": [CELL]}
    assert bench.module("layer_metrics", NAME) is moe_row_tiles_visited_share
    for cell in bench.doc["workloads"]:
        names = {m["name"] for m in bench.metrics("per_layer", cell["name"])}
        assert (NAME in names) == (cell["name"] == CELL)
    # The bound it is measured against keeps its reader and its meaning.
    assert "moe_dispatch_rows" in {
        m["name"] for m in bench.metrics("per_layer", CELL)}
