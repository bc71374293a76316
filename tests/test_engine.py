"""Integration: the minimum end-to-end slice from SURVEY.md §7 — MNIST-shape
data, 2-layer MLP, 10 simulated clients on one device via vmap, FedAvg
in-XLA, accuracy rising across rounds (BASELINE config #1 scaled down)."""

import dataclasses
import re

import numpy as np
import pytest

from colearn_federated_learning_tpu.fed import engine, evaluation
from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def tiny_config(**fed_kw) -> ExperimentConfig:
    fed = dict(strategy="fedavg", rounds=4, local_epochs=1, batch_size=32,
               lr=0.05, momentum=0.9)
    fed.update(fed_kw)
    return ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=10, partition="iid"),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=32, depth=2),
        fed=FedConfig(**fed),
        run=RunConfig(name="test", seed=0),
    )


def test_mnist_mlp_end_to_end_accuracy_rises():
    learner = FederatedLearner(tiny_config(rounds=8))
    _, acc0 = learner.evaluate()
    history = learner.fit(rounds=8)
    _, acc1 = learner.evaluate()
    assert len(history) == 8
    assert np.isfinite(history[-1]["train_loss"])
    assert acc1 > acc0 + 0.2, (acc0, acc1)
    assert acc1 > 0.5


def test_cohort_sampling_runs_and_learns():
    learner = FederatedLearner(tiny_config(cohort_size=4, rounds=6))
    assert learner.cohort_size == 4
    learner.fit(rounds=6)
    _, acc = learner.evaluate()
    assert acc > 0.4


def test_fedprox_and_server_opt_strategies_run():
    for strat, kw in [("fedprox", {"prox_mu": 0.01}),
                      ("fedadam", {"server_lr": 0.05}),
                      ("fedyogi", {"server_lr": 0.05})]:
        learner = FederatedLearner(tiny_config(strategy=strat, rounds=2, **kw))
        hist = learner.fit(rounds=2)
        assert np.isfinite(hist[-1]["train_loss"]), strat


def test_straggler_dropout_reduces_completed():
    cfg = tiny_config(rounds=1, straggler_prob=0.9, straggler_min_fraction=0.9)
    learner = FederatedLearner(cfg)
    rec = learner.run_round()
    assert rec["completed"] < 10  # most clients failed to finish
    assert np.isfinite(rec["train_loss"])


def test_determinism_same_seed_same_result():
    cfg = tiny_config(rounds=2)
    l1 = FederatedLearner(cfg)
    l2 = FederatedLearner(cfg)
    l1.fit(rounds=2)
    l2.fit(rounds=2)
    a1 = l1.evaluate()
    a2 = l2.evaluate()
    assert a1 == a2


def test_weighted_aggregation_respects_counts():
    """A zero-weight client must not affect the weighted aggregate: the
    weighted sum with weights [w0, w1, 0] equals the one with [w0, w1]."""
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.utils import pytrees

    rng = np.random.default_rng(0)
    stacked3 = {"w": jnp.asarray(rng.normal(size=(3, 4, 2)), jnp.float32)}
    stacked2 = {"w": stacked3["w"][:2]}
    w3 = jnp.asarray([2.0, 5.0, 0.0])
    w2 = jnp.asarray([2.0, 5.0])
    got = pytrees.tree_weighted_sum(stacked3, w3)["w"]
    want = pytrees.tree_weighted_sum(stacked2, w2)["w"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    got_m = pytrees.tree_weighted_mean(stacked3, w3)["w"]
    want_m = pytrees.tree_weighted_mean(stacked2, w2)["w"]
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m), rtol=1e-6)


def test_all_stragglers_round_is_noop_under_secure_agg():
    """If every sampled client is a straggler, the round must be a no-op:
    the secure-agg mask-cancellation residual must NOT be amplified by the
    near-zero total weight (regression: engine's zero-contributor gate)."""
    import jax

    cfg = tiny_config(rounds=1, straggler_prob=1.0, straggler_min_fraction=1.0,
                      secure_agg=True, dp_clip=1.0)
    learner = FederatedLearner(cfg)
    before = jax.tree.map(np.asarray, learner.server_state.params)
    rec = learner.run_round()
    assert rec["completed"] == 0
    assert rec["total_weight"] == 0
    after = jax.tree.map(np.asarray, learner.server_state.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)


def test_thousand_client_build_runs_a_round():
    """North-star scale on the client axis (BASELINE.json: 1000-client
    FedAvg): the vmap engine must build and run a cohort-64 round with
    1000 resident clients.  Tiny model/shard keeps CI fast — the point is
    the client-axis shapes, not the FLOPs."""
    from colearn_federated_learning_tpu.utils.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, RunConfig,
    )

    cfg = ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=1000,
                        partition="iid", max_examples_per_client=8),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=8, depth=1),
        fed=FedConfig(strategy="fedavg", rounds=1, cohort_size=64,
                      local_steps=1, batch_size=4, lr=0.05, momentum=0.9),
        run=RunConfig(name="thousand", backend="cpu"),
    )
    learner = FederatedLearner(cfg)
    assert learner.num_clients == 1000 and learner.cohort_size == 64
    rec = learner.run_round()
    assert rec["completed"] == 64
    assert np.isfinite(rec["train_loss"])


def test_bert_working_set_of_rows_matches_the_dense_trainer():
    """Two ``fit()`` rounds of a tiny BERT whose vocabulary (2,000) exceeds
    what a client's round can touch (2 steps x 4 x 64 tokens): the trainer
    takes a working set of the token table's rows (fed/local.py), and the
    federation is the one the dense trainer gives."""
    import jax

    from colearn_federated_learning_tpu import telemetry
    from colearn_federated_learning_tpu.fed import programs
    from colearn_federated_learning_tpu.fed import setup as setup_lib

    cfg = ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=8, partition="iid",
                        max_examples_per_client=16),
        model=ModelConfig(name="bert", num_classes=4, width=32, depth=1,
                          num_heads=4, seq_len=64, vocab_size=2000),
        fed=FedConfig(strategy="fedavg", rounds=2, cohort_size=4, local_steps=2,
                      batch_size=4, lr=1e-3, momentum=0.0,
                      local_optimizer="adam", lr_schedule="warmup_cosine",
                      warmup_rounds=2),
        run=RunConfig(name="bert_rows", seed=3),
    )
    compacted = telemetry.get_registry().counter("local.compact_tables")
    before = compacted.value
    learner = FederatedLearner(cfg)
    learner.fit(rounds=2)
    assert compacted.value - before == 1
    assert telemetry.get_registry().snapshot()["local.compact_rows"] == 2 * 4 * 64
    assert learner._round_fn.compiles == 1

    dense = FederatedLearner(cfg)

    class Undeclared(type(dense.model)):
        gathered_tables = {}

    twin = Undeclared(**{f.name: getattr(dense.model, f.name)
                         for f in dataclasses.fields(dense.model)
                         if f.name not in ("parent", "name")})
    update, _ = setup_lib.local_trainer_for_config(
        cfg, twin.apply, dense.shards.capacity)
    dense._round_fn = telemetry.CompileTracker(
        programs.build_round_fn(dense.plan, update), name="engine.round")
    dense.fit(rounds=2)
    assert compacted.value - before == 1              # the dense one did not
    np.testing.assert_allclose([r["train_loss"] for r in learner.history],
                               [r["train_loss"] for r in dense.history],
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(learner.server_state.params),
                    jax.tree.leaves(dense.server_state.params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["vmap", "mesh4"])
def test_names_the_benchmark_reads(on_mesh, cpu_devices):
    """``benchmarks/`` tells the programs of a device trace by the jitted
    functions' names and reads these attributes off the learner: a rename
    here is a change of the yardstick."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.array(cpu_devices[:4]), ("clients",)) if on_mesh else None
    learner = FederatedLearner(tiny_config(cohort_size=4), mesh=mesh)

    def module_name(lowered):
        return re.match(r"module @(\S+)", lowered.as_text()).group(1)

    round_program = module_name(learner._round_fn.lower(
        learner.server_state, learner.base_key, jnp.asarray(0, jnp.int32),
        *learner._device_data, None, None, learner._dp_clip))
    assert round_program == ("jit_body" if on_mesh else "jit_round_fn")
    assert "eval" in module_name(
        learner._eval_fn.lower(learner.server_state.params))
    assert (learner._round_fn.name, learner._eval_fn.name) == (
        "engine.round", "engine.eval")
    assert engine.make_eval_fn is evaluation.make_eval_fn    # faults.py patches it
    for name in ("params", "history", "dataset", "model", "server_state",
                 "devices", "num_steps", "cohort_size", "fit", "from_config"):
        assert hasattr(learner, name), name
    assert len(learner.devices) == (4 if on_mesh else 1)
    assert learner.cohort_size == 4 == learner.plan.cohort_size
