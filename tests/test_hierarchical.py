"""Hierarchical edge→cloud federation (fed/hierarchical.py).

The reference aggregates flat; HierFAVG-style two-tier rounds are a
rebuild superset matching CoLearn's edge-gateway deployment picture.
"""

import numpy as np
import pytest

from colearn_federated_learning_tpu.fed.hierarchical import HierarchicalLearner
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def _cfg(**fed_kw):
    fed = dict(strategy="fedavg", rounds=6, cohort_size=0, local_steps=3,
               batch_size=16, lr=0.1, momentum=0.9)
    fed.update(fed_kw)
    return ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=8, partition="iid",
                        max_examples_per_client=64),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=32, depth=2),
        fed=FedConfig(**fed),
        run=RunConfig(name="hier_test"),
    )


def _params_flat(tree):
    import jax

    return np.concatenate([np.ravel(np.asarray(a))
                           for a in jax.tree.leaves(tree)])


def test_hierarchical_learns_and_syncs():
    h = HierarchicalLearner(_cfg(), num_groups=2, sync_period=2)
    assert len(h.groups) == 2 and h.groups[0].real_num_clients == 4
    hist = h.fit(rounds=6)
    # Sync happened on every period boundary and the cloud model learns.
    assert [r["synced"] for r in hist] == [False, True] * 3
    loss, acc = h.evaluate()
    assert acc > 0.9, acc

    # After a sync boundary every group holds the identical cloud model.
    a = _params_flat(h.groups[0].server_state.params)
    b = _params_flat(h.groups[1].server_state.params)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, _params_flat(h.global_params))


def test_groups_diverge_between_syncs():
    h = HierarchicalLearner(_cfg(), num_groups=2, sync_period=4)
    h.run_round()                       # round 0: no sync
    a = _params_flat(h.groups[0].server_state.params)
    b = _params_flat(h.groups[1].server_state.params)
    assert np.abs(a - b).max() > 0.0    # distinct edge populations diverge


def test_wan_traffic_is_periodic():
    # sync_period=3 over 6 rounds: exactly 2 cloud syncs.
    h = HierarchicalLearner(_cfg(), num_groups=2, sync_period=3)
    hist = h.fit(rounds=6)
    assert sum(r["synced"] for r in hist) == 2


def test_terminal_sync_folds_the_last_partial_period():
    # rounds=5, period=2: boundary syncs after rounds 1 and 3; round 4
    # would otherwise leave the last period's training out of the
    # reported cloud model — fit() must terminally sync.
    h = HierarchicalLearner(_cfg(), num_groups=2, sync_period=2)
    hist = h.fit(rounds=5)
    assert [r["synced"] for r in hist] == [False, True, False, True, True]
    assert "eval_acc" in hist[-1]
    a = _params_flat(h.groups[0].server_state.params)
    np.testing.assert_array_equal(a, _params_flat(h.global_params))


def test_rejects_indivisible_client_count():
    with pytest.raises(ValueError, match="divisible"):
        HierarchicalLearner(_cfg(), num_groups=3)   # 8 % 3 != 0


def test_rejects_stateful_strategies():
    with pytest.raises(ValueError, match="server state"):
        HierarchicalLearner(_cfg(strategy="fedadam"), num_groups=2)
    with pytest.raises(ValueError, match="num_groups"):
        HierarchicalLearner(_cfg(), num_groups=1)


def test_hierarchical_composes_with_robust_aggregation():
    # Each edge group is a full engine: per-group Byzantine-robust
    # aggregation composes with the cloud sync for free.
    import dataclasses

    cfg = _cfg()
    cfg = cfg.replace(fed=dataclasses.replace(cfg.fed, aggregator="median"))
    h = HierarchicalLearner(cfg, num_groups=2, sync_period=2)
    assert all(g.plan.robust for g in h.groups)
    hist = h.fit(rounds=6)
    assert np.isfinite(hist[-1]["train_loss"])
    _, acc = h.evaluate()
    assert acc > 0.85, acc
