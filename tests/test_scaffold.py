"""SCAFFOLD: control-variate algebra, engine integration (vmap + mesh),
and the drift-correction property it exists for."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from colearn_federated_learning_tpu.fed import local as local_lib
from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.utils import pytrees
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def _cfg(strategy="scaffold", num_clients=8, cohort=4, alpha=0.05, seed=0):
    return ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=num_clients,
                        partition="dirichlet", dirichlet_alpha=alpha),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=32, depth=2),
        fed=FedConfig(strategy=strategy, rounds=10, cohort_size=cohort,
                      local_steps=5, batch_size=16, lr=0.05, momentum=0.0),
        run=RunConfig(name=f"scaffold_{strategy}", backend="cpu", seed=seed),
    )


def test_scaffold_local_update_algebra():
    """With zero variates the correction is a no-op (matches plain SGD) and
    option II reproduces c' = -delta/(K*lr)."""
    import optax

    def apply_fn(vars_, x, train=False):
        return x @ vars_["params"]["w"]

    w = {"w": jnp.eye(4)}
    lr = 0.1
    opt = optax.sgd(lr)
    plain = local_lib.make_local_update(apply_fn, opt, num_steps=4,
                                        batch_size=8)
    scaf = local_lib.make_local_update(apply_fn, opt, num_steps=4,
                                       batch_size=8, scaffold=True, lr=lr)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    y = jax.random.randint(jax.random.PRNGKey(1), (32,), 0, 4)
    count = jnp.asarray(32)
    key = jax.random.PRNGKey(2)
    budget = jnp.asarray(4, jnp.int32)

    zeros = pytrees.tree_zeros_like(w)
    r_plain = plain(w, x, y, count, key, budget)
    sr = scaf(w, x, y, count, key, budget, zeros, zeros)
    np.testing.assert_allclose(np.asarray(sr.result.delta["w"]),
                               np.asarray(r_plain.delta["w"]), rtol=1e-6)
    expected_c = -np.asarray(sr.result.delta["w"]) / (4 * lr)
    np.testing.assert_allclose(np.asarray(sr.c_new["w"]), expected_c,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sr.delta_c["w"]), expected_c,
                               rtol=1e-5)

    # A nonzero shared correction (c - c_i) shifts every SGD step by
    # -lr*(c - c_i) per step relative to plain SGD when gradients are
    # unaffected... verify the correction enters: different c => different delta.
    ones = jax.tree.map(jnp.ones_like, w)
    sr2 = scaf(w, x, y, count, key, budget, zeros, ones)
    assert not np.allclose(np.asarray(sr2.result.delta["w"]),
                           np.asarray(sr.result.delta["w"]))


def test_scaffold_requires_lr():
    import optax

    with pytest.raises(ValueError, match="lr"):
        local_lib.make_local_update(lambda *a, **k: None, optax.sgd(0.1),
                                    num_steps=1, batch_size=1, scaffold=True)


def test_scaffold_engine_converges_and_beats_fedavg_under_drift():
    """Strong non-IID partition + partial participation: SCAFFOLD's whole
    point.  It must converge, keep finite state, and not lose to FedAvg."""
    scaf = FederatedLearner(_cfg("scaffold"))
    fed = FederatedLearner(_cfg("fedavg"))
    for _ in range(10):
        scaf.run_round()
        fed.run_round()
    loss_s, acc_s = scaf.evaluate()
    loss_f, acc_f = fed.evaluate()
    assert np.isfinite(loss_s)
    c_norm = float(pytrees.tree_global_norm(scaf.client_c))
    assert np.isfinite(c_norm) and c_norm > 0  # variates actually moved
    assert acc_s >= acc_f - 0.05  # parity-or-better under drift


def test_scaffold_mesh_matches_vmap(cpu_devices):
    cfg = _cfg(cohort=0)                       # full participation
    mesh = Mesh(np.array(cpu_devices[:4]), ("clients",))
    a = FederatedLearner(cfg)
    b = FederatedLearner(cfg, mesh=mesh)
    for _ in range(3):
        ra = a.run_round()
        rb = b.run_round()
    np.testing.assert_allclose(ra["train_loss"], rb["train_loss"], rtol=1e-4)
    # global control variates agree across placements
    ca = np.asarray(a.server_state.control["Dense_0"]["kernel"])
    cb = np.asarray(b.server_state.control["Dense_0"]["kernel"])
    np.testing.assert_allclose(ca, cb, rtol=1e-4, atol=1e-6)
    la, aa = a.evaluate()
    lb, ab = b.evaluate()
    np.testing.assert_allclose(la, lb, rtol=1e-3)


def test_scaffold_rejected_by_stateless_paths(tmp_path):
    from colearn_federated_learning_tpu.comm.worker import DeviceWorker
    from colearn_federated_learning_tpu.fed import offline

    cfg = _cfg()
    g0 = str(tmp_path / "g.npz")
    offline.init_global_model(cfg, g0)        # init itself is fine
    with pytest.raises(NotImplementedError, match="scaffold"):
        offline.client_update(cfg, 0, g0, str(tmp_path / "u.npz"))
    with pytest.raises(NotImplementedError, match="scaffold"):
        DeviceWorker(cfg, 0)


def test_scaffold_checkpoint_roundtrip(tmp_path):
    cfg = _cfg()
    cfg = cfg.replace(run=dataclasses.replace(
        cfg.run, checkpoint_dir=str(tmp_path / "ckpt")))
    a = FederatedLearner(cfg)
    a.run_round(); a.run_round()
    a.save_checkpoint()

    b = FederatedLearner(cfg)
    step = b.restore_checkpoint()
    assert step == 2
    np.testing.assert_allclose(
        np.asarray(a.server_state.control["Dense_0"]["kernel"]),
        np.asarray(b.server_state.control["Dense_0"]["kernel"]),
    )
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(a.client_c)[0]),
        np.asarray(jax.tree.leaves(b.client_c)[0]),
    )
    b.run_round()                              # resumes cleanly


def test_scaffold_variates_are_cohort_resident(mesh8):
    """Flagship regime: many clients, small cohort.  The full variate store
    must live on HOST (numpy) and the round program must only ever see the
    cohort block — num_clients=512 x model on-device would not fit the
    flagship configs."""
    cfg = _cfg(num_clients=512, cohort=16)
    cfg = cfg.replace(data=dataclasses.replace(cfg.data, partition="iid"))
    learner = FederatedLearner(cfg, mesh=mesh8)
    # host-resident store, full size
    leaves = jax.tree.leaves(learner.client_c)
    assert all(isinstance(l, np.ndarray) for l in leaves)
    assert all(l.shape[0] == learner.num_clients for l in leaves)

    before = jax.tree.map(np.array, learner.client_c)
    rec = learner.run_round()
    assert rec["completed"] >= 1

    # exactly the sampled cohort's rows changed
    _, rows = learner._host_sample_cohort(0)
    changed = set()
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(learner.client_c)):
        diff = np.abs(a - b).reshape(a.shape[0], -1).sum(axis=1)
        changed |= set(np.nonzero(diff)[0].tolist())
    assert changed, "no variates moved"
    assert changed <= set(rows.tolist())
    assert len(changed) <= learner.cohort_size

    # and the jitted round program's variate operand is cohort-sized
    sel, rows = learner._host_sample_cohort(1)
    assert sel.shape[0] == learner.cohort_size == 16
