"""Adaptive DP clipping (privacy/dp.py quantile tracking + engine wiring).

The reference ships fixed clip hooks at best (SURVEY.md §2 "DP hooks");
adaptive clipping is a rebuild superset: the clip norm is a device scalar
threaded operand→metric through the jit round program, tracking a target
quantile of client update norms (Andrew et al. pattern, PAPERS.md —
formulas only).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.privacy import dp as dp_lib
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def _cfg(**fed_kw):
    fed = dict(strategy="fedavg", rounds=6, cohort_size=0, local_steps=2,
               batch_size=8, lr=0.1, momentum=0.0,
               dp_clip=100.0, dp_adaptive_clip=True, dp_clip_lr=0.5,
               dp_target_quantile=0.5)
    fed.update(fed_kw)
    return ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=8, partition="iid",
                        max_examples_per_client=32),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=32, depth=2),
        fed=FedConfig(**fed),
        run=RunConfig(name="adaptive_clip_test"),
    )


def test_noise_split_formula():
    # z_delta > z always (part of the budget goes to the bit query), and
    # the joint mechanism matches z: z^-2 == z_delta^-2 + (2*sigma_b)^-2.
    z, sb = 1.0, 2.0
    zd = dp_lib.adaptive_noise_multiplier(z, sb)
    assert zd > z
    np.testing.assert_allclose(zd ** -2 + (2 * sb) ** -2, z ** -2, rtol=1e-12)
    with pytest.raises(ValueError, match="bit_noise"):
        dp_lib.adaptive_noise_multiplier(1.0, 0.4)  # needs sigma_b > z/2


def test_clip_update_direction():
    clip = jnp.float32(1.0)
    # Everyone under the clip (frac 1.0 > target 0.5): clip must shrink.
    down = dp_lib.adaptive_clip_update(clip, jnp.float32(1.0), 0.5, 0.2)
    # Nobody under (frac 0.0 < target): clip must grow.
    up = dp_lib.adaptive_clip_update(clip, jnp.float32(0.0), 0.5, 0.2)
    assert float(down) < 1.0 < float(up)


def test_engine_adapts_clip_toward_quantile():
    # Start with a clip far above every update norm: the bit fraction sits
    # at 1.0 and the clip must decay geometrically round over round.
    learner = FederatedLearner(_cfg())
    hist = learner.fit(rounds=6)
    clips = [r["dp_clip"] for r in hist]
    assert all(np.isfinite(clips))
    assert clips[-1] < clips[0] * 0.3, clips
    assert hist[0]["dp_bit_frac"] == 1.0
    # ... and training still works.
    assert np.isfinite(hist[-1]["train_loss"])


def test_engine_grows_tiny_clip():
    # Start with a clip far below every norm: fraction 0, clip must grow.
    learner = FederatedLearner(_cfg(dp_clip=1e-3))
    hist = learner.fit(rounds=4)
    assert hist[-1]["dp_clip"] > hist[0]["dp_clip"]
    assert hist[0]["dp_bit_frac"] == 0.0


def test_adaptive_with_noise_accounts_single_mechanism():
    # With noise on, the accountant keeps charging the configured z (the
    # bit query's cost is folded in by the inflated update noise).
    cfg = _cfg(dp_noise_multiplier=0.8, dp_bit_noise=2.0)
    learner = FederatedLearner(cfg)
    assert learner.plan.dp_z > 0.8          # inflated update noise
    rec = learner.run_round()
    assert rec["dp_epsilon"] > 0.0 and np.isfinite(rec["dp_epsilon"])


def test_mesh_adaptive_matches_single_device(cpu_devices):
    from jax.sharding import Mesh

    cfg = _cfg()
    ref = FederatedLearner(cfg)
    mesh = Mesh(np.array(cpu_devices[:8]), ("clients",))
    m = FederatedLearner(cfg, mesh=mesh)
    for _ in range(3):
        r_ref = ref.run_round()
        r_m = m.run_round()
    np.testing.assert_allclose(r_m["dp_clip"], r_ref["dp_clip"], rtol=1e-6)
    np.testing.assert_allclose(r_m["train_loss"], r_ref["train_loss"],
                               rtol=1e-4)


def test_secure_agg_composition_masks_bits_and_matches():
    # Adaptive clipping composes with secure aggregation: the quantile
    # bits ride their own pairwise-mask stream and cancel in the sum, so
    # the clip trajectory matches the unmasked run up to the float32
    # mask-cancellation residual.
    plain = FederatedLearner(_cfg())
    masked = FederatedLearner(_cfg(secure_agg=True))
    for _ in range(3):
        r_p = plain.run_round()
        r_m = masked.run_round()
    np.testing.assert_allclose(r_m["dp_bit_frac"], r_p["dp_bit_frac"],
                               atol=5e-3)
    np.testing.assert_allclose(r_m["dp_clip"], r_p["dp_clip"], rtol=1e-3)
    np.testing.assert_allclose(r_m["train_loss"], r_p["train_loss"],
                               rtol=1e-3)

    # ... and each INDIVIDUAL masked bit is actually hidden: the per-lane
    # payload sits nowhere near {0, 1} (trajectory equality alone would
    # also hold if masking silently regressed to a no-op).
    from colearn_federated_learning_tpu.privacy import secure_agg as sa

    partners = jnp.asarray([1, 2], jnp.int32)
    m = sa.mask_scalar(jnp.float32(1.0), masked.base_key, jnp.int32(0),
                       partners, jnp.int32(0), std=1e3)
    assert min(abs(float(m)), abs(float(m) - 1.0)) > 1.0


def test_round_metrics_include_update_norms_only_when_private_safe():
    # Plain runs report pre-clip norm telemetry ...
    learner = FederatedLearner(_cfg(dp_clip=0.0, dp_adaptive_clip=False))
    rec = learner.run_round()
    assert rec["delta_norm_max"] >= rec["delta_norm_mean"] > 0.0
    # ... DP runs must NOT: exact un-noised norms are an unaccounted
    # release the epsilon report would not cover.
    dp = FederatedLearner(_cfg())
    rec = dp.run_round()
    assert "delta_norm_mean" not in rec and "delta_norm_max" not in rec
