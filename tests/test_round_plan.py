"""The seam under the engine (fed/programs.py): a round is built from a
``RoundPlan`` and a ``local_update`` — no learner, dataset or model — the
cohort draw is one function for the program and the host, and
``plan_round`` owns every refusal of the federation's options.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from colearn_federated_learning_tpu.fed import programs, strategies
from colearn_federated_learning_tpu.fed.local import LocalResult
from colearn_federated_learning_tpu.utils.config import (
    ExperimentConfig,
    FedConfig,
)

NUM_CLIENTS, CAPACITY = 8, 3


def _plan(mesh=None, num_clients=NUM_CLIENTS, real_num_clients=None, **fed):
    return programs.plan_round(
        ExperimentConfig(fed=FedConfig(**fed)), num_clients=num_clients,
        real_num_clients=real_num_clients or num_clients, num_steps=1,
        mesh=mesh)


def _mesh(cpu_devices, *axes):
    axes = axes or ("clients",)
    shape = (4,) if len(axes) == 1 else (2, 2)
    return Mesh(np.array(cpu_devices[:4]).reshape(shape), axes)


def toy_update(params, x, y, count, key, budget, lr_scale):
    """A client whose "training" moves w to the mean of its own rows."""
    rows = (jnp.arange(x.shape[0]) < count)[:, None]
    mean = jnp.sum(x * rows, axis=0) / jnp.maximum(count, 1)
    return LocalResult({"w": mean - params["w"]}, count, jnp.bool_(True),
                       jnp.sum(mean), jnp.float32(1.0))


@pytest.mark.parametrize("on_mesh", [False, True], ids=["vmap", "mesh4"])
def test_round_from_a_plan_is_the_weighted_mean(on_mesh, cpu_devices):
    plan = _plan(mesh=_mesh(cpu_devices) if on_mesh else None)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(NUM_CLIENTS, CAPACITY, 2)).astype(np.float32)
    counts = np.array([3, 1, 2, 0, 3, 3, 1, 2], np.int32)   # one ghost
    state = strategies.init_server_state(
        {"w": jnp.asarray([10.0, -10.0])}, plan.fed)

    round_fn = programs.build_round_fn(plan, toy_update)
    assert round_fn.__name__ == ("body" if on_mesh else "round_fn")
    new_state, metrics, new_c = round_fn(
        state, jax.random.PRNGKey(0), jnp.asarray(0, jnp.int32), x,
        np.zeros((NUM_CLIENTS, CAPACITY), np.int32), counts,
        np.arange(NUM_CLIENTS, dtype=np.int32), None, None, jnp.float32(0.0))

    means = np.stack([x[i, :n].mean(axis=0) if n else np.zeros(2)
                      for i, n in enumerate(counts)])
    by_hand = (means * counts[:, None]).sum(axis=0) / counts.sum()
    np.testing.assert_allclose(new_state.params["w"], by_hand, rtol=1e-5)
    assert new_c is None
    assert int(metrics["completed"]) == 7          # the ghost never counts
    assert float(metrics["total_weight"]) == counts.sum()
    np.testing.assert_allclose(
        metrics["train_loss"],
        (means.sum(axis=1) * counts).sum() / counts.sum(), rtol=1e-5)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["1dev", "4dev"])
@pytest.mark.parametrize("cohort", [0, 4], ids=["everyone", "sampled"])
def test_one_cohort_draw_eager_and_traced(cohort, on_mesh, cpu_devices):
    mesh = _mesh(cpu_devices) if on_mesh else None
    plan = _plan(mesh=mesh, cohort_size=cohort, real_num_clients=6)
    # Interleaved placement puts the two ghosts on different devices.
    counts = jnp.asarray([3, 1, 2, 0, 3, 3, 1, 0], jnp.int32)
    key, r = jax.random.PRNGKey(7), jnp.asarray(5, jnp.int32)

    if not on_mesh:
        eager = programs.draw_cohort(plan, key, r, counts)
        traced = jax.jit(lambda k, i, c: programs.draw_cohort(plan, k, i, c))(
            key, r, counts)
    else:
        L = plan.local_clients
        eager = jnp.concatenate([
            programs.draw_cohort(plan, key, r, counts[d * L:(d + 1) * L], d)
            for d in range(plan.clients_size)])
        traced = jax.jit(jax.shard_map(
            lambda k, i, c: programs.draw_cohort(
                plan, k, i, c, device=jax.lax.axis_index("clients")),
            mesh=mesh, in_specs=(P(), P(), P("clients")),
            out_specs=P("clients"), check_vma=False))(key, r, counts)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(traced))

    per_device = plan.cohort_per_device
    assert eager.shape == (per_device * plan.clients_size,)
    if cohort == 0:
        np.testing.assert_array_equal(
            eager, np.tile(np.arange(plan.local_clients), plan.clients_size))
    else:
        # Sampled: distinct slots of each device's block, real clients only.
        blocks = np.asarray(eager).reshape(plan.clients_size, per_device)
        for d, block in enumerate(blocks):
            assert len(set(block.tolist())) == per_device
            mine = np.asarray(counts)[d * plan.local_clients:][block]
            assert (mine > 0).all()


REFUSED = {
    "ring_of_odd_degree": (
        dict(secure_agg=True, secure_agg_neighbors=3),
        "secure_agg_neighbors must be an even integer >= 2, got 3"),
    "threshold_out_of_range": (
        dict(secure_agg=True, secure_agg_threshold=0.0),
        r"secure_agg_threshold must be in \(0, 1\], got 0.0"),
    "scaffold_with_secure_agg": (
        dict(strategy="scaffold", secure_agg=True),
        "scaffold is incompatible with secure_agg/dp hooks"),
    "scaffold_with_dp": (
        dict(strategy="scaffold", dp_clip=1.0),
        "scaffold is incompatible with secure_agg/dp hooks"),
    "scaffold_on_a_model_axis": (
        dict(strategy="scaffold"),
        r"scaffold with a model \(TP\) axis is unsupported"),
    "unknown_aggregator": (
        dict(aggregator="mode"), "unknown aggregator 'mode'"),
    "trim_of_a_half": (
        dict(aggregator="median", trim_fraction=0.5),
        r"trim_fraction must be in \[0, 0.5\), got 0.5"),
    "robust_with_secure_agg": (
        dict(aggregator="median", secure_agg=True),
        "secure-agg masks only cancel in a plain sum"),
    "robust_with_scaffold": (
        dict(aggregator="median", strategy="scaffold"),
        "scaffold assumes mean aggregation"),
    "robust_with_dp_noise": (
        dict(aggregator="median", dp_clip=1.0, dp_noise_multiplier=0.5),
        "not the Gaussian mechanism the RDP accountant models"),
    "trim_that_trims_nobody": (
        dict(aggregator="trimmed_mean", trim_fraction=0.1),
        r"trims zero clients at cohort_size=8; raise it to at least "
        r"0\.125000"),
    "krum_with_no_attacker": (
        dict(aggregator="krum", trim_fraction=0.1, cohort_size=4),
        r"assumes zero Byzantine clients \(f = 0\) at cohort_size=4"),
    "trim_on_a_cohort_of_two": (
        dict(aggregator="trimmed_mean", cohort_size=2),
        r"needs a cohort of at least 3 \(got 2\)"),
    "adaptive_clip_without_a_norm": (
        dict(dp_adaptive_clip=True),
        "dp_adaptive_clip needs dp_clip > 0 as the initial norm"),
    "bit_noise_under_half_of_z": (
        dict(dp_adaptive_clip=True, dp_clip=1.0, dp_noise_multiplier=1.0,
             dp_bit_noise=0.4),
        "bit_noise"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_plan_round_refuses(case, cpu_devices):
    fed, message = REFUSED[case]
    mesh = (_mesh(cpu_devices, "clients", "model")
            if case == "scaffold_on_a_model_axis" else None)
    with pytest.raises(ValueError, match=message):
        _plan(mesh=mesh, **fed)


def test_plan_round_derives_the_cohort_and_the_dp_quantities(cpu_devices):
    plan = _plan(cohort_size=5, real_num_clients=6)
    assert (plan.cohort_size, plan.cohort_per_device, plan.dp_cohort) == (
        5, 5, 5)
    assert plan.seq_axis is None and plan.x_spec == P("clients")
    assert plan.track_norms and not plan.uniform_weights

    with pytest.warns(UserWarning, match="cohort_size=6 is not a multiple "
                      r"of the 4-way client axis; using 4 \(1/device\)"):
        on_mesh = _plan(mesh=_mesh(cpu_devices), cohort_size=6)
    assert (on_mesh.cohort_size, on_mesh.cohort_per_device,
            on_mesh.local_clients) == (4, 1, 2)

    private = _plan(dp_adaptive_clip=True, dp_clip=1.0,
                    dp_noise_multiplier=0.8, cohort_size=4,
                    real_num_clients=3)
    assert private.dp_cohort == 3 and private.dp_bit_noise == 1.0
    assert private.dp_z > 0.8                  # inflated update noise
    assert private.uniform_weights and not private.track_norms
