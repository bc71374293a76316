"""Device time by the program's own scopes: ``telemetry.device_scope``, the
scope table ``CompileTracker.scopes`` reads from a compiled program's
text, the scopes the engine, the share layer and the language models'
heads enter, and what ``colearn trace-summary`` makes of them.

The parser is held to real lines: ``tests/data/pr34_round_fn_op_names.tsv``
is cut from the round program XLA compiled for the v5e at PR 34."""

import contextlib
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from colearn_federated_learning_tpu import cli, telemetry
from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.telemetry import Scope, export, runtime
from colearn_federated_learning_tpu.telemetry.registry import MetricsRegistry
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
    get_config,
)

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "pr34_round_fn_op_names.tsv")
ENGINE_SCOPES = {"cohort", "local", "local.optimizer", "aggregate",
                 "server", "head"}
MOE_SCOPES = {"moe", "moe.route", "moe.pairs", "moe.tiles", "moe.shared"}


def names_in(table):
    return runtime._names(table.values())


def real_lines():
    with open(DATA) as f:
        rows = [line.rstrip("\n").split("\t") for line in f
                if not line.startswith("#")]
    return [pytest.param(phase, tuple(filter(None, path.split("|"))),
                         op_name, id=name)
            for name, phase, path, op_name in rows]


# ------------------------------------------------------------- the parser --
@pytest.mark.parametrize("phase,path,op_name", real_lines())
def test_parse_real_op_names(phase, path, op_name):
    scope = (runtime.parse_op_name(op_name) if op_name
             else runtime.parse_hlo_text("  %x.1 = f32[] copy(%y)")[1]["x.1"])
    assert scope == Scope(phase, path)
    assert not any("(" in name or name in ("while", "body", "closed_call",
                                           "checkpoint") for name in path)


def test_parse_the_issues_example():
    """Backward pass, rematerialised, layer 3, the share layer's routed
    part, inside the tile loop; the engine's scope in front."""
    assert runtime.parse_op_name(
        "jit(round_fn)/local/vmap()/while/body/closed_call/"
        "transpose(jvp(NemotronH))/jvp(NemotronH)/checkpoint/"
        "rematted_computation/layer_3/moe/mixer/mixer.routed_latent/"
        "moe.tiles/while/body/closed_call/gather") == Scope(
        "remat", ("local", "NemotronH", "layer_3", "moe", "mixer",
                  "mixer.routed_latent", "moe.tiles"))
    # A scope right under a transformation is wrapped by it.
    assert runtime.parse_op_name(
        "jit(round_fn)/local/vmap()/while/body/closed_call/jvp(head)/"
        "reduce_sum") == Scope("forward", ("local", "head"))
    assert runtime.parse_op_name("copy") == runtime.NO_SCOPE


def test_parse_hlo_text_names_every_instruction():
    """An instruction XLA made without a path of its own is filed under
    what runs its computation: the grouped product under its loop, a
    fusion's parameter under the fusion; in the entry computation it stays
    without a name."""
    tiles = "jit(round_fn)/local/vmap()/jvp(M)/layer_1/moe/moe.tiles"
    text = "\n".join([
        "HloModule jit_round_fn, is_scheduled=true, entry_computation_lay"
        "out={(f32[8]{0})->f32[8]{0}}",
        "",
        "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        '  ROOT %mul.3 = f32[8]{0} multiply(%param_0, %param_0), metadata='
        '{op_name="jit(round_fn)/server/mul" source_file="x.py"}',
        "}",
        "",
        "%wide.region_22.79.sunk (arg.1: (s32[], f32[8])) -> (s32[], f32[8])"
        " {",
        "  %arg.1 = (s32[], f32[8]{0}) parameter(0)",
        '  %ragged-dot-none.48 = f32[8]{0} custom-call(%arg.1), custom_call'
        '_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}',
        f'  %add.5 = f32[8]{{0}} add(%x, %y), metadata={{op_name="{tiles}/'
        'while/body/closed_call/add"}',
        "  ROOT %tuple.6 = (s32[], f32[8]{0}) tuple(%i, %add.5)",
        "}",
        "",
        "%region_25.80 (arg.2: (s32[], f32[8])) -> pred[] {",
        "  ROOT %lt.7 = pred[] compare(%a, %b), direction=LT",
        "}",
        "",
        "ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {",
        "  %Arg_0.1 = f32[8]{0} parameter(0)",
        '  fusion.7 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused'
        '_computation.1, metadata={op_name="jit(round_fn)/cohort/jit(_take)'
        '/gather" stack_frame_id=7}',
        "  %while.8 = (s32[], f32[8]{0}) while(%t), condition=%region_25.80,"
        " body=%wide.region_22.79.sunk, metadata={op_name="
        f'"{tiles}/while"}}',
        "  ROOT %copy.2 = f32[8]{0} copy(%fusion.7)",
        "}",
    ])
    module, table = runtime.parse_hlo_text(text)
    assert module == "jit_round_fn"
    loop = Scope("forward", ("local", "M", "layer_1", "moe", "moe.tiles"))
    assert table == {
        "param_0": Scope("none", ("cohort",)),
        "mul.3": Scope("none", ("server",)),
        "arg.1": loop, "ragged-dot-none.48": loop, "add.5": loop,
        "tuple.6": loop, "lt.7": loop, "while.8": loop,
        "Arg_0.1": runtime.NO_SCOPE,
        "fusion.7": Scope("none", ("cohort",)),
        "copy.2": runtime.NO_SCOPE}


# ---------------------------------------------------------- device_scope ---
def scoped(name):
    """One function under the scope ``name`` (None: under none)."""
    def f(x, s):
        with (telemetry.device_scope(name) if name
              else contextlib.nullcontext()):
            y = jnp.sin(x) * s

        def body(c, _):
            with telemetry.device_scope("t.inner"):
                return c * 2 + jnp.cos(c), None

        y, _ = jax.lax.scan(body, y, None, length=3)
        return jax.grad(lambda z: jnp.sum(jnp.tanh(z @ z.T)))(y)

    return jax.jit(f)


def test_a_scope_is_debug_information_only():
    """The lowered text, which the default compile-cache key is made of,
    is the same with the scope and without; the name is declared when the
    function is traced, not when it is written."""
    x = jnp.ones((8, 8))
    f = scoped("t.lowered_alike")
    assert "t.lowered_alike" not in telemetry.declared_scopes()
    with_scope = f.lower(x, 2.0).as_text()
    assert "t.lowered_alike" in telemetry.declared_scopes()
    assert with_scope == scoped(None).lower(x, 2.0).as_text()
    assert "t.lowered_alike" in f.lower(x, 2.0).as_text(debug_info=True)


@pytest.fixture
def cache_in(tmp_path):
    """A persistent compile cache of this test's own."""
    previous = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", previous)
    compilation_cache.reset_cache()


def test_the_table_is_of_this_source_whatever_the_cache_holds(cache_in):
    """jax's cache key drops debug information: the second function, which
    differs from the first in a scope's name alone, is handed the first's
    executable, names and all.  Its table shows its own names."""
    x = jnp.ones((16, 16))
    reg = MetricsRegistry()

    def called(scope, fn):
        tracker = runtime.CompileTracker(scoped(scope), fn, registry=reg)
        tracker(x, 2.0)
        return tracker

    # One call site for all three: the lines of a call stack are metadata
    # too.  The third stands for the next process of the second's source.
    first, second, third = (called(scope, fn) for scope, fn in (
        ("t.first", "t"), ("t.second", "t"), ("t.second", "t3")))
    snap = reg.snapshot()
    assert snap["telemetry.cache_miss_total{fn=t}"] == 1
    assert snap["telemetry.cache_hit_total{fn=t}"] == 1
    assert {"t.first", "t.inner"} <= names_in(first.scopes())
    assert reg.snapshot()[
        "telemetry.scope_table_total{fn=t,how=loaded}"] == 1
    # What the second ran is the first's executable ...
    assert "t.first" in second._aot(*second._first_call)[1].as_text()
    # ... and its table is its own.
    table = second.scopes()
    assert "t.second" in names_in(table)
    assert "t.first" not in names_in(table)
    assert {s.phase for s in table.values()} >= {"forward", "backward",
                                                 "none"}
    after = reg.snapshot()
    assert after["telemetry.scope_table_total{fn=t,how=built}"] == 1
    assert after["telemetry.scope_table_instructions{fn=t}"] == len(table)
    assert 0 < after["telemetry.scope_table_unnamed{fn=t}"] < len(table)
    assert after["telemetry.scope_table_seconds{fn=t}"] > 0
    # Neither the round's compile nor its cache events: as they were.
    for key in snap:
        if "compile_total" in key or "cache_" in key:
            assert after[key] == snap[key], key
    # Asked again: the same table, nothing built.
    assert second.scopes() is table
    assert reg.snapshot() == after
    # The fresh executable is in the cache now.
    assert third.scopes() == table
    assert reg.snapshot()[
        "telemetry.scope_table_total{fn=t3,how=loaded}"] == 1


def test_cost_analysis_and_the_table_share_one_executable():
    reg = MetricsRegistry()
    f = runtime.CompileTracker(scoped("t.shared_aot"), "t", registry=reg)
    x = jnp.ones((8, 8))
    assert f.scopes() == {}                     # never called: no program
    f(x, 2.0)
    cost = f.cost_analysis(x, 2.0)
    [(lowered, compiled, seconds)] = f._aot_cache.values()
    assert cost["compile_s"] == seconds
    f.scopes()
    assert list(f._aot_cache.values()) == [(lowered, compiled, seconds)]
    assert f.compiles == 1
    # A wrapped function that cannot be lowered has no table.
    plain = runtime.CompileTracker(lambda x: x, "plain", registry=reg)
    plain(1)
    assert plain.scopes() == {} and plain.cost_analysis(1) == {}


# ------------------------------------------------- the programs' scopes ----
def cnn_experiment():
    return ExperimentConfig(
        data=DataConfig(dataset="cifar10_tiny", num_clients=4,
                        partition="iid", max_examples_per_client=16),
        model=ModelConfig(name="cnn", num_classes=10, width=8),
        fed=FedConfig(strategy="fedavg", rounds=2, cohort_size=2,
                      local_steps=2, batch_size=4, lr=0.05, momentum=0.9),
        run=RunConfig(name="scopes_cnn", eval_every=1))


def bert_experiment():
    return ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=4,
                        partition="iid", max_examples_per_client=16),
        model=ModelConfig(name="bert", num_classes=4, width=32, depth=1,
                          num_heads=4, seq_len=64, vocab_size=2000),
        fed=FedConfig(strategy="fedavg", rounds=2, cohort_size=2,
                      local_steps=2, batch_size=4, lr=1e-3, momentum=0.0,
                      local_optimizer="adam"),
        run=RunConfig(name="scopes_bert", eval_every=1))


def evabyte_experiment():
    from tests.test_evabyte import _experiment
    return _experiment(cohort=1)


def nemotron_experiment():
    from tests.test_nemotron_h import _experiment
    return _experiment(cohort=1)


def xing_experiment():
    from tests.test_xing4 import _experiment
    return _experiment()


FAMILIES = {
    # family: (its experiment, scopes beyond the engine's, phases)
    "cnn": (cnn_experiment, set(), {"forward", "backward"}),
    "bert": (bert_experiment, set(), {"forward", "backward"}),
    "evabyte": (evabyte_experiment, set(), {"forward", "backward", "remat"}),
    "nemotron_h": (nemotron_experiment, MOE_SCOPES | {"ssd"},
                   {"forward", "backward", "remat"}),
    "xing4": (xing_experiment, MOE_SCOPES | {"mla", "mhc", "mtp"},
              {"forward", "backward", "remat"}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_learners_table_holds_every_scope_it_entered(family):
    experiment, more, phases = FAMILIES[family]
    learner = FederatedLearner(experiment())
    learner.fit(rounds=1)
    table = learner._round_fn.scopes()
    assert ENGINE_SCOPES | more <= names_in(table)
    assert phases | {"none"} == {scope.phase for scope in table.values()}
    by_name = {}
    for scope in set(table.values()):
        for name in scope.path:
            by_name.setdefault(name, set()).add(scope.phase)
    # The model's passes lie under ``local``, the optimiser's in none.
    assert by_name["local"] >= phases
    assert by_name["local.optimizer"] == {"none"}
    assert by_name["cohort"] == by_name["server"] == {"none"}
    if more:
        # The tile loop is named in the forward and in its own backward.
        assert {"forward", "backward"} <= by_name["moe.tiles"]
    # The evaluation program gets the model's scopes and none of the
    # engine's.
    tables = telemetry.program_scopes()
    assert tables["jit_round_fn"] is table
    assert not names_in(tables["jit_eval_fn"]) & (
        ENGINE_SCOPES - {"head"})
    assert more - {"mtp"} <= names_in(tables["jit_eval_fn"]) | {"mtp"}


def test_the_mesh_program_is_named_body_and_aggregates_by_psum(mesh8):
    """``from_config`` on the suite's eight devices: the round program is
    ``jit_body``, its collectives lie under ``aggregate``."""
    learner = FederatedLearner(cnn_experiment(), mesh=mesh8)
    learner.fit(rounds=1)
    table = telemetry.program_scopes()["jit_body"]
    assert table is learner._round_fn.scopes()
    assert ENGINE_SCOPES <= names_in(table)
    reduces = {name: scope for name, scope in table.items()
               if name.startswith("all-reduce")}
    assert reduces and all("aggregate" in scope.path
                           for scope in reduces.values())


@pytest.fixture(scope="module")
def mlp():
    """A learner that has fitted two rounds, and the registry's state then."""
    cfg = get_config("mnist_mlp_fedavg")
    learner = FederatedLearner(cfg.replace(
        data=dataclasses.replace(cfg.data, dataset="mnist_tiny",
                                 num_clients=4),
        fed=dataclasses.replace(cfg.fed, rounds=2, local_steps=2,
                                batch_size=8, cohort_size=2),
        run=dataclasses.replace(cfg.run, backend="cpu", eval_every=1,
                                name="scopes_mlp")))
    learner.fit(rounds=2)
    return learner


def engine_counters():
    return {key: value
            for key, value in telemetry.get_registry().snapshot().items()
            if "fn=engine." in key and "scope_table" not in key}


def test_asking_for_the_table_is_not_the_rounds_compile(mlp):
    before = engine_counters()
    assert before["telemetry.compile_total{fn=engine.round}"] >= 1
    table = mlp._round_fn.scopes()
    assert {"cohort", "local", "aggregate", "server"} <= names_in(table)
    assert engine_counters() == before
    mlp.fit(rounds=1)                       # no recompile after the ask
    assert mlp._round_fn.compiles == 1
    assert "recompiles" not in mlp.history[-1]
    after = engine_counters()
    for key in before:
        if "compile_total" in key or "cache_" in key:
            assert after[key] == before[key], key


def test_fit_in_a_profiler_session_asks_for_no_table(mlp, tmp_path):
    """The traced window of the benchmark: nothing compiles, no table is
    built; the tables are there for whoever asks afterwards."""
    from benchmarks.harness.runner import watch_compiles

    tables = telemetry.get_registry().counter("telemetry.scope_table_total")
    mlp._round_fn._scope_tables.clear()
    before = tables.value
    jax.profiler.start_trace(str(tmp_path))
    try:
        with watch_compiles() as seen:
            mlp.fit(rounds=2)
    finally:
        jax.profiler.stop_trace()
    assert seen["compiles"] == 0
    assert tables.value == before
    assert not os.path.exists(tmp_path / export.SCOPES_FILE)
    assert mlp._round_fn.scopes() and tables.value == before + 1


def test_the_table_survives_its_learner():
    learner = FederatedLearner(cnn_experiment())
    learner.fit(rounds=1)
    tracker = learner._round_fn
    del learner
    tables = telemetry.program_scopes()
    assert tables["jit_round_fn"] is tracker.scopes()
    assert ENGINE_SCOPES <= names_in(tables["jit_round_fn"])
    assert set(tables) >= {"jit_round_fn", "jit_eval_fn"}
    # No array is kept: the first call's arguments in the abstract.
    args, kwargs = tracker._first_call
    assert not kwargs and not any(
        isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(args))
    assert any(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree.leaves(args))


# ------------------------------------------------- the operator's summary --
def test_a_profile_dir_run_leaves_the_tables_beside_the_profile(tmp_path):
    cfg = cnn_experiment()
    learner = FederatedLearner(cfg.replace(
        run=dataclasses.replace(cfg.run, profile_dir=str(tmp_path))))
    learner.fit(rounds=3)                   # the window is rounds 1..2
    tables = export.load_program_scopes(tmp_path / export.SCOPES_FILE)
    assert tables == telemetry.program_scopes()
    # The CPU's profile has no device plane: the summary says so.
    assert "no device plane" in telemetry.summarize_profile(str(tmp_path))
    assert cli.main(["trace-summary", str(tmp_path)]) == 0
    assert cli.main(["trace-summary", str(tmp_path / "plugins")]) == 2


def test_device_seconds_go_by_the_execution_an_operation_ran_in():
    """Two programs share ``fusion.1``; a ``while`` holds two children."""
    us = 1e3
    ops = [
        ("%fusion.1 = f32[8]{0} fusion(%p)", 10 * us, 4 * us),
        ("%while.1 = (f32[8]{0}) while(%t)", 14 * us, 40 * us),
        ("%fusion.2 = f32[8]{0} fusion(%p)", 14 * us, 10 * us),
        ("%fusion.3 = f32[8]{0} fusion(%p)", 24 * us, 28 * us),
        ("%copy.9 = f32[8]{0} copy(%p)", 54 * us, 6 * us),
        ("%fusion.1 = f32[8]{0} fusion(%p)", 70 * us, 20 * us),
        ("%fusion.1 = f32[8]{0} fusion(%p)", 95 * us, 1 * us),
    ]
    modules = [("jit_round_fn(1)", 10 * us, 50 * us),
               ("jit_eval_fn(2)", 70 * us, 20 * us),
               ("jit_other(3)", 95 * us, 1 * us)]
    cohort = Scope("none", ("cohort",))
    route = Scope("forward", ("local", "M", "layer_0", "moe", "moe.route"))
    tiles = Scope("backward", ("local", "M", "layer_0", "moe", "moe.tiles"))
    head = Scope("none", ("M", "head"))
    tables = {
        "jit_round_fn": {"fusion.1": cohort, "fusion.2": route,
                         "fusion.3": tiles,
                         "while.1": Scope("none", ("local",))},
        "jit_eval_fn": {"fusion.1": head}}
    totals = export.device_seconds_by_scope(ops, modules, tables)
    assert totals == {
        ("jit_round_fn", cohort): pytest.approx(4e-6),
        ("jit_round_fn", Scope("none", ("local",))): pytest.approx(2e-6),
        ("jit_round_fn", route): pytest.approx(10e-6),
        ("jit_round_fn", tiles): pytest.approx(28e-6),
        ("jit_round_fn", None): pytest.approx(6e-6),    # copy.9: no entry
        ("jit_eval_fn", head): pytest.approx(20e-6)}
    text = "\n".join(export.render_scope_seconds(totals, top=3, depth=4))
    assert "jit_round_fn: 0.000050 s" in text and "88.0% under" in text
    assert "backward 0.000028 s (56.0%)" in text
    assert "local/M/layer_0/moe" in text and "cohort" in text
    assert "jit_eval_fn" in text and "M/head" in text


def test_tables_round_trip_through_their_file(tmp_path, monkeypatch):
    tables = {"jit_round_fn": {"fusion.1": Scope("remat", ("local", "ssd")),
                               "copy.2": runtime.NO_SCOPE,
                               "fusion.3": Scope("remat", ("local", "ssd"))}}
    monkeypatch.setattr(export, "program_scopes", lambda: tables)
    path = export.write_program_scopes(str(tmp_path))
    assert export.load_program_scopes(path) == tables
    with open(path) as f:
        assert len(json.load(f)["programs"]["jit_round_fn"]["scopes"]) == 2
