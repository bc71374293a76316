"""Byzantine-robust aggregation (fed/robust.py + engine wiring).

The reference's mean aggregator lets one malicious IoT device steer the
global model arbitrarily; the rebuild adds coordinate-wise median and
trimmed mean.  Tests: statistics vs numpy oracles (with masking), a
label-flip poisoning attack the median survives and the mean does not,
and mesh/vmap equivalence.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.fed.robust import robust_aggregate
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def test_robust_statistics_match_numpy():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(9, 4, 3)).astype(np.float32)
    mask = np.array([1, 1, 1, 0, 1, 1, 0, 1, 1], bool)   # 7 contributors
    tree = {"a": jnp.asarray(x), "b": jnp.asarray(x[:, 0])}

    med = robust_aggregate(tree, jnp.asarray(mask), "median")
    np.testing.assert_allclose(np.asarray(med["a"]),
                               np.median(x[mask], axis=0), atol=1e-6)

    tm = robust_aggregate(tree, jnp.asarray(mask), "trimmed_mean",
                          trim_fraction=0.2)
    k = int(np.floor(0.2 * mask.sum()))                  # 1 per side
    ref = np.sort(x[mask], axis=0)[k:mask.sum() - k].mean(axis=0)
    np.testing.assert_allclose(np.asarray(tm["a"]), ref, atol=1e-6)

    # No contributors -> zeros, not NaN.
    zed = robust_aggregate(tree, jnp.zeros(9, bool), "median")
    assert float(np.abs(np.asarray(zed["a"])).max()) == 0.0


def test_krum_excludes_outliers():
    rng = np.random.default_rng(1)
    # 7 honest updates clustered at +1, 2 attackers far away.
    x = (1.0 + 0.01 * rng.normal(size=(9, 16))).astype(np.float32)
    x[0] = 50.0
    x[4] = -50.0
    tree = {"w": jnp.asarray(x)}
    out = robust_aggregate(tree, jnp.ones(9, bool), "krum",
                           trim_fraction=0.25)      # f = floor(.25*9) = 2
    got = np.asarray(out["w"])
    honest = np.delete(x, [0, 4], axis=0)
    # Multi-Krum selects n-f = 7 best-scored: exactly the honest cluster.
    np.testing.assert_allclose(got, honest.mean(axis=0), atol=1e-4)

    # Masked rows never participate (attacker hidden behind the mask).
    mask = np.ones(9, bool); mask[0] = False
    out = robust_aggregate(tree, jnp.asarray(mask), "krum",
                           trim_fraction=0.2)
    assert np.abs(np.asarray(out["w"])).max() < 10.0

    # Float32-overflow attacker: sum(x*x) = inf must yield a WORSE score,
    # not a zero one (distance clamping, not zeroing).
    x2 = (1.0 + 0.01 * rng.normal(size=(6, 16))).astype(np.float32)
    x2[2] = 1e25                      # sq overflows float32
    out = robust_aggregate({"w": jnp.asarray(x2)}, jnp.ones(6, bool),
                           "krum", trim_fraction=0.2)
    got = np.asarray(out["w"])
    assert np.isfinite(got).all() and np.abs(got).max() < 10.0


def _cfg(aggregator="mean", num_clients=8):
    return ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=num_clients,
                        partition="iid", max_examples_per_client=64),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=32, depth=2),
        fed=FedConfig(strategy="fedavg", rounds=5, cohort_size=0,
                      local_steps=3, batch_size=16, lr=0.1, momentum=0.9,
                      aggregator=aggregator),
        run=RunConfig(name=f"robust_{aggregator}"),
    )


class _LabelFlipLearner(FederatedLearner):
    """Flip the labels of the first ``n_bad`` clients AFTER partitioning —
    a classic data-poisoning attacker inside the simulation."""

    def __init__(self, config, n_bad: int, **kw):
        self._n_bad = n_bad
        super().__init__(config, **kw)
        x, y, counts, ids = self._device_data
        yh = np.array(y)                              # writable copy
        bad = np.isin(np.asarray(self.client_ids), np.arange(n_bad))
        yh[bad] = (9 - yh[bad]) % 10                  # deterministic flip
        y = jnp.asarray(yh)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            y = jax.device_put(
                y, NamedSharding(self.mesh, P(self.plan.client_axis))
            )
        self._device_data = (x, y, counts, ids)


def test_median_survives_label_flip_poisoning():
    # 3 of 8 clients flip every label.  The mean aggregator degrades badly;
    # the coordinate-wise median keeps learning.  (Measured on this seed:
    # mean 0.665, median 0.857 after 8 rounds.)
    mean_l = _LabelFlipLearner(_cfg("mean"), n_bad=3)
    mean_l.fit(rounds=8)
    _, acc_mean = mean_l.evaluate()

    med_l = _LabelFlipLearner(_cfg("median"), n_bad=3)
    med_l.fit(rounds=8)
    _, acc_med = med_l.evaluate()

    assert acc_med > 0.8, acc_med
    assert acc_med > acc_mean + 0.1, (acc_med, acc_mean)

    # Trimmed mean needs trim >= attacker fraction to help: with 3/8
    # attackers, trim 0.4 trims 3 per side; 0.1 trims none (k = 0).
    tm_cfg = _cfg("trimmed_mean")
    tm_cfg = tm_cfg.replace(
        fed=dataclasses.replace(tm_cfg.fed, trim_fraction=0.4))
    tm_l = _LabelFlipLearner(tm_cfg, n_bad=3)
    tm_l.fit(rounds=8)
    _, acc_tm = tm_l.evaluate()
    assert acc_tm > acc_mean + 0.1, (acc_tm, acc_mean)


def test_krum_survives_label_flip_in_engine():
    # Krum with f = floor(0.4*8) = 3 against 3 label-flippers.
    cfg = _cfg("krum")
    cfg = cfg.replace(fed=dataclasses.replace(cfg.fed, trim_fraction=0.4))
    k_l = _LabelFlipLearner(cfg, n_bad=3)
    k_l.fit(rounds=8)
    _, acc = k_l.evaluate()
    assert acc > 0.8, acc


def test_trimmed_mean_learns_clean():
    cfg = _cfg("trimmed_mean")
    cfg = cfg.replace(fed=dataclasses.replace(cfg.fed, trim_fraction=0.2))
    learner = FederatedLearner(cfg)
    learner.fit(rounds=8)
    _, acc = learner.evaluate()
    assert acc > 0.85, acc


def test_robust_mesh_matches_vmap(cpu_devices):
    from jax.sharding import Mesh

    cfg = _cfg("median")
    ref = FederatedLearner(cfg)
    mesh = Mesh(np.array(cpu_devices[:8]), ("clients",))
    m = FederatedLearner(cfg, mesh=mesh)
    for _ in range(2):
        r_ref = ref.run_round()
        r_m = m.run_round()
    np.testing.assert_allclose(r_m["train_loss"], r_ref["train_loss"],
                               rtol=1e-5)
    p1 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(m.server_state.params)])
    p2 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(ref.server_state.params)])
    np.testing.assert_allclose(p1, p2, atol=1e-6)


def test_trim_clamps_under_runtime_dropouts():
    # Construction-time validation only sees the STATIC cohort size; at
    # runtime stragglers can shrink n_valid so floor(trim * n_valid) hits
    # 0 — e.g. trim 0.2 with 4 survivors.  The statistic must still trim
    # one row per side rather than silently degrade to a plain mean.
    rng = np.random.default_rng(3)
    x = rng.normal(size=(8, 5)).astype(np.float32)
    x[1] = 1e4                        # outlier a real trim removes
    mask = np.zeros(8, bool); mask[:4] = True
    out = robust_aggregate({"w": jnp.asarray(x)}, jnp.asarray(mask),
                           "trimmed_mean", trim_fraction=0.2)
    got = np.asarray(out["w"])
    ref = np.sort(x[:4], axis=0)[1:3].mean(axis=0)    # k clamped to 1
    np.testing.assert_allclose(got, ref, atol=1e-6)
    # trim_fraction == 0 is an explicit "no trimming" request: no clamp.
    out0 = robust_aggregate({"w": jnp.asarray(x)}, jnp.asarray(mask),
                            "trimmed_mean", trim_fraction=0.0)
    np.testing.assert_allclose(np.asarray(out0["w"]), x[:4].mean(axis=0),
                               rtol=1e-5)


def test_krum_clamps_f_under_runtime_dropouts():
    # Same hazard for Krum: floor(0.2 * 4) = 0 would select ALL survivors
    # (plain mean, attacker included); the clamp assumes >= 1 attacker.
    rng = np.random.default_rng(7)
    x = (1.0 + 0.01 * rng.normal(size=(8, 6))).astype(np.float32)
    x[2] = 100.0                      # attacker among the 4 survivors
    mask = np.zeros(8, bool); mask[:4] = True
    out = robust_aggregate({"w": jnp.asarray(x)}, jnp.asarray(mask),
                           "krum", trim_fraction=0.2)
    got = np.asarray(out["w"])
    np.testing.assert_allclose(got.mean(), 1.0, atol=0.05)


def test_krum_survives_nan_rows():
    # A masked row (dropped straggler) full of NaN must not poison the
    # selection matmul (0 * NaN = NaN without sanitization).
    rng = np.random.default_rng(5)
    x = (1.0 + 0.01 * rng.normal(size=(6, 8))).astype(np.float32)
    x[3] = np.nan
    mask = np.ones(6, bool); mask[3] = False
    out = robust_aggregate({"w": jnp.asarray(x)}, jnp.asarray(mask),
                           "krum", trim_fraction=0.25)
    got = np.asarray(out["w"])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got.mean(), 1.0, atol=0.1)


def test_krum_excludes_valid_nonfinite_attacker():
    # An UNMASKED attacker submitting inf/NaN must be excluded by score,
    # not sanitized into an innocent-looking zero row that gets selected.
    rng = np.random.default_rng(9)
    x = (1.0 + 0.01 * rng.normal(size=(6, 8))).astype(np.float32)
    x[1] = np.inf
    out = robust_aggregate({"w": jnp.asarray(x)}, jnp.ones(6, bool),
                           "krum", trim_fraction=0.2)
    got = np.asarray(out["w"])
    assert np.isfinite(got).all()
    # Aggregate stays at the honest cluster (~1.0), NOT diluted toward 0
    # by a zeroed attacker row.
    np.testing.assert_allclose(got.mean(), 1.0, atol=0.05)
