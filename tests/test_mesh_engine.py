"""Scale-sim (SURVEY.md §7): the multi-chip shard_map engine on a faked
8-device CPU mesh — psum aggregation must match the single-device vmap
engine's math."""

import dataclasses

import numpy as np

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from tests.test_engine import tiny_config


def test_sharded_engine_learns(mesh8):
    learner = FederatedLearner(tiny_config(rounds=4), mesh=mesh8)
    # 10 clients pad to 16 (2 per device), ghosts carry zero weight.
    assert learner.num_clients == 16
    hist = learner.fit(rounds=4)
    # Ghosts contribute exactly nothing: the aggregate weight is the sum of
    # REAL clients' example counts.
    assert hist[0]["total_weight"] == float(learner.shards.counts.sum())
    _, acc = learner.evaluate()
    assert acc > 0.5


def test_sharded_full_participation_matches_vmap(mesh8):
    """With full participation and no stragglers, the mesh engine computes
    the same weighted average as the vmap engine (same clients, same keys),
    so round-1 training losses must agree to float tolerance."""
    cfg = tiny_config(rounds=1)
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, num_clients=8)
    )
    lv = FederatedLearner(cfg)
    lm = FederatedLearner(cfg, mesh=mesh8)
    rv = lv.run_round()
    rm = lm.run_round()
    assert rm["total_weight"] == rv["total_weight"]
    np.testing.assert_allclose(rm["train_loss"], rv["train_loss"], rtol=1e-4)
    # And the resulting global params agree.
    import jax

    for a, b in zip(
        jax.tree.leaves(lv.server_state.params), jax.tree.leaves(lm.server_state.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_sharded_round_program_compiles_once(mesh8):
    """The mesh round program is built once: the initial server state is
    placed where the program leaves it (replicated over the mesh), so
    round 1 does not compile a second executable for a new argument
    placement — on the chip that was a second full compile, invisible to
    a shape/dtype signature."""
    learner = FederatedLearner(tiny_config(rounds=3), mesh=mesh8)
    hist = learner.fit(rounds=3)
    assert learner._round_fn.compiles == 1
    assert learner._round_fn._cache_size() == 1
    assert all("recompiles" not in rec for rec in hist)
    # Each device received only its own clients' block of the data.
    x = learner._device_data[0]
    assert {s.data.shape[0] for s in x.addressable_shards} == {
        x.shape[0] // 8}


def test_sharded_privacy_path_runs(mesh8):
    cfg = tiny_config(rounds=2, dp_clip=1.0, dp_noise_multiplier=0.1,
                      secure_agg=True)
    learner = FederatedLearner(cfg, mesh=mesh8)
    hist = learner.fit(rounds=2)
    assert np.isfinite(hist[-1]["train_loss"])
    # Ghost clients (counts==0) must be excluded from uniform weighting.
    assert hist[-1]["total_weight"] <= 10


def test_sharded_partial_cohort_is_device_stratified(mesh8):
    """Partial cohorts on a mesh are sampled PER DEVICE (stratified): each
    device contributes exactly cohort/D of its own resident clients, and —
    because real clients are interleaved across devices — every sampled
    slot is a real client whenever each device holds >= cohort/D reals.
    This is a deliberate semantic difference from the vmap engine's global
    without-replacement sample (no cross-device data movement); this test
    pins the contract."""
    cfg = tiny_config(rounds=3, cohort_size=8)
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, num_clients=24)
    )
    learner = FederatedLearner(cfg, mesh=mesh8)
    assert learner.cohort_per_device == 1    # 8 slots over 8 devices
    hist = learner.fit(rounds=3)
    for rec in hist:
        # all 8 sampled slots are real clients -> all complete, and the
        # total weight is the sum of exactly 8 real shard counts
        assert rec["completed"] == 8
        assert rec["total_weight"] > 0
    _, acc = learner.evaluate()
    assert np.isfinite(acc)
