"""RDP accountant: closed-form Gaussian point, subsampling amplification,
composition monotonicity, and the engine's per-round (ε, δ) reporting."""

import dataclasses
import math

import numpy as np
import pytest

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.privacy.accountant import (
    RdpAccountant,
    rdp_to_eps_delta,
    subsampled_gaussian_rdp,
)
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


# ---- closed forms ----------------------------------------------------------
def test_full_batch_rdp_is_exact_gaussian():
    """q=1 collapses to the exact Gaussian RDP α/(2σ²)."""
    for sigma in (0.5, 1.0, 2.0, 4.0):
        for order in (2, 3, 8, 64):
            got = subsampled_gaussian_rdp(1.0, sigma, order)
            assert got == pytest.approx(order / (2 * sigma**2), rel=1e-12)


def test_gaussian_eps_matches_analytic_minimum():
    """For q=1, ε(δ) = min_α [Tα/2σ² + log(1/δ)/(α-1)] has the closed form
    at α* = 1 + sqrt(2σ²·log(1/δ)/T); the integer-order grid must land
    within a few percent above it."""
    sigma, delta, T = 2.0, 1e-5, 10
    acc = RdpAccountant(sigma, 1.0, delta=delta)
    acc.step(T)
    a_star = 1.0 + math.sqrt(2 * sigma**2 * math.log(1 / delta) / T)
    eps_star = T * a_star / (2 * sigma**2) + math.log(1 / delta) / (a_star - 1)
    eps = acc.epsilon()
    assert eps >= eps_star - 1e-9          # discrete grid can't beat analytic
    assert eps <= eps_star * 1.05


def test_small_q_quadratic_amplification():
    """At order 2 the series is exact: log(1 + q²(e-1) + O(q³)) ≈ q²(e-1)."""
    q, sigma = 0.01, 1.0
    got = subsampled_gaussian_rdp(q, sigma, 2)
    expect = math.log(
        (1 - q) ** 2 + 2 * q * (1 - q) + q**2 * math.exp(1.0 / sigma**2)
    )
    assert got == pytest.approx(expect, rel=1e-12)
    assert got < 2 / (2 * sigma**2) * 0.01  # amplification is dramatic


def test_accountant_monotonicity_and_edges():
    base = RdpAccountant(1.0, 0.25)
    base.step(10)
    more_rounds = RdpAccountant(1.0, 0.25)
    more_rounds.step(50)
    quieter = RdpAccountant(2.0, 0.25)
    quieter.step(10)
    bigger_cohort = RdpAccountant(1.0, 0.5)
    bigger_cohort.step(10)
    assert base.epsilon() < more_rounds.epsilon()
    assert quieter.epsilon() < base.epsilon()
    assert base.epsilon() < bigger_cohort.epsilon()

    assert RdpAccountant(1.0, 0.25).epsilon() == 0.0       # no rounds yet
    zero_noise = RdpAccountant(0.0, 0.25)
    zero_noise.step()
    assert math.isinf(zero_noise.epsilon())
    with pytest.raises(ValueError):
        rdp_to_eps_delta(np.ones(3), np.arange(2, 5, dtype=float), 0.0)
    with pytest.raises(ValueError):
        subsampled_gaussian_rdp(1.2, 1.0, 2)


# ---- engine integration ----------------------------------------------------
def _cfg(**fed_kw):
    fed = dict(strategy="fedavg", rounds=3, cohort_size=4, local_steps=2,
               batch_size=8, lr=0.05, momentum=0.9)
    fed.update(fed_kw)
    return ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=16, partition="iid"),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=16, depth=1),
        fed=FedConfig(**fed),
        run=RunConfig(name="dp_acct", backend="cpu"),
    )


def test_engine_reports_cumulative_epsilon():
    learner = FederatedLearner(
        _cfg(dp_clip=1.0, dp_noise_multiplier=1.0, dp_delta=1e-5)
    )
    eps = []
    for _ in range(3):
        rec = learner.run_round()
        assert rec["dp_delta"] == 1e-5
        eps.append(rec["dp_epsilon"])
    assert all(np.isfinite(e) and e > 0 for e in eps)
    assert eps[0] < eps[1] < eps[2]        # budget strictly accumulates
    # matches a freshly composed accountant for the same mechanism
    ref = RdpAccountant(1.0, learner.plan.dp_cohort / learner.real_num_clients,
                        delta=1e-5)
    ref.step(3)
    assert eps[-1] == pytest.approx(ref.epsilon(), rel=1e-12)


def test_engine_omits_epsilon_without_dp():
    learner = FederatedLearner(_cfg())
    rec = learner.run_round()
    assert "dp_epsilon" not in rec and learner.accountant is None


def test_epsilon_survives_checkpoint_resume(tmp_path):
    cfg = _cfg(dp_clip=1.0, dp_noise_multiplier=1.0)
    cfg = cfg.replace(run=dataclasses.replace(
        cfg.run, checkpoint_dir=str(tmp_path / "ckpt")))
    a = FederatedLearner(cfg)
    a.run_round()
    a.run_round()
    a.save_checkpoint()
    eps_2 = a.history[-1]["dp_epsilon"]

    b = FederatedLearner(cfg)
    assert b.restore_checkpoint() == 2
    rec = b.run_round()                    # round 2 overall
    assert rec["dp_epsilon"] > eps_2       # continues, doesn't restart at 0


def test_coordinator_reports_and_checkpoints_epsilon(tmp_path):
    """Socket plane: per-round ε with the ACTUAL cohort fraction, and the
    accumulated RDP state survives kill-and-resume."""
    from colearn_federated_learning_tpu.comm.broker import MessageBroker
    from colearn_federated_learning_tpu.comm.coordinator import (
        FederatedCoordinator,
    )
    from colearn_federated_learning_tpu.comm.worker import DeviceWorker

    cfg = _cfg(dp_clip=1.0, dp_noise_multiplier=1.0, cohort_size=2, rounds=3)
    cfg = cfg.replace(
        data=dataclasses.replace(cfg.data, num_clients=3),
        run=dataclasses.replace(cfg.run,
                                checkpoint_dir=str(tmp_path / "ckpt"),
                                checkpoint_every=1),
    )
    with MessageBroker() as broker:
        workers = [
            DeviceWorker(cfg, i, broker.host, broker.port).start()
            for i in range(3)
        ]
        try:
            coord = FederatedCoordinator(cfg, broker.host, broker.port,
                                         round_timeout=60.0,
                                         want_evaluator=False)
            coord.enroll(min_devices=3, timeout=20.0)
            r0 = coord.run_round()
            r1 = coord.run_round()
            assert 0 < r0["dp_epsilon"] < r1["dp_epsilon"]
            coord.save_checkpoint()
            eps_at_kill = r1["dp_epsilon"]
            coord.close()

            coord2 = FederatedCoordinator(cfg, broker.host, broker.port,
                                          round_timeout=60.0,
                                          want_evaluator=False)
            assert coord2.restore_checkpoint() == 2
            assert coord2.accountant.epsilon() == pytest.approx(eps_at_kill)
            coord2.enroll(min_devices=3, timeout=20.0)
            rec = coord2.run_round()
            assert rec["dp_epsilon"] > eps_at_kill
            coord2.close()
        finally:
            for w in workers:
                w.stop()


def test_coordinator_charges_realized_not_nominal_noise():
    """Workers calibrate noise to the NOMINAL cohort; when fewer enroll the
    realized central noise is smaller and ε must be charged accordingly
    (higher), not at the nominal σ."""
    from colearn_federated_learning_tpu.comm.broker import MessageBroker
    from colearn_federated_learning_tpu.comm.coordinator import (
        FederatedCoordinator,
    )
    from colearn_federated_learning_tpu.comm.worker import DeviceWorker

    cfg = _cfg(dp_clip=1.0, dp_noise_multiplier=1.0, cohort_size=0)
    cfg = cfg.replace(data=dataclasses.replace(cfg.data, num_clients=3))
    with MessageBroker() as broker:
        workers = [  # nominal cohort is 3 (all clients); only 2 enroll
            DeviceWorker(cfg, i, broker.host, broker.port).start()
            for i in range(2)
        ]
        try:
            coord = FederatedCoordinator(cfg, broker.host, broker.port,
                                         round_timeout=60.0,
                                         want_evaluator=False)
            coord.enroll(min_devices=2, timeout=20.0)
            rec = coord.run_round()
            assert rec["completed"] == 2

            sigma_eff = 1.0 * math.sqrt(2.0 / 3.0)
            ref_eff = RdpAccountant(1.0, 1.0, delta=cfg.fed.dp_delta)
            ref_eff.step(sampling_rate=1.0, noise_multiplier=sigma_eff)
            assert rec["dp_epsilon"] == pytest.approx(ref_eff.epsilon())

            ref_nominal = RdpAccountant(1.0, 1.0, delta=cfg.fed.dp_delta)
            ref_nominal.step()
            assert rec["dp_epsilon"] > ref_nominal.epsilon()
            coord.close()
        finally:
            for w in workers:
                w.stop()
