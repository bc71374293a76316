"""Config → runtime pieces shared by the in-process engine and the
cross-silo offline path.

The same ExperimentConfig must produce the SAME partition, step budget and
local trainer whether clients are simulated on-device (fed/engine.py) or
run as decoupled silos against model files (fed/offline.py) — otherwise a
silo trains differently from its simulated twin.  Both paths call these
helpers instead of re-deriving the pieces.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from colearn_federated_learning_tpu.data import partition as partition_lib
from colearn_federated_learning_tpu.fed import local as local_lib
from colearn_federated_learning_tpu.utils.config import ExperimentConfig


def local_model_config(model_cfg):
    """Model config as seen by a SINGLE process (no mesh): ring/ulysses
    attention need a shard_map sequence axis, so SP configs fall back to
    the dense core — the param pytree is identical across cores, so
    checkpoints and wire payloads stay compatible (models/attention.py)."""
    import dataclasses

    if model_cfg.attn_impl in ("ring", "ulysses"):
        return dataclasses.replace(model_cfg, attn_impl="dense")
    return model_cfg


def partition_for_config(
    config: ExperimentConfig, labels: np.ndarray
) -> list[np.ndarray]:
    """Per-client index lists for ``config.data``
    (iid | dirichlet | pathological)."""
    c = config.data
    if c.partition != "iid" and np.ndim(labels) > 1:
        raise ValueError(
            f"data.partition {c.partition!r} splits by an example's class; "
            f"labels of shape {np.shape(labels)[1:]} per example (a label "
            "per token) have none: use 'iid'")
    if c.partition == "dirichlet":
        return partition_lib.dirichlet_partition(
            labels, c.num_clients, c.dirichlet_alpha, seed=config.run.seed
        )
    if c.partition == "pathological":
        # McMahan-style sort-and-deal 2-shard split (the literature-anchor
        # protocol, scripts/validate_literature.py).
        return partition_lib.pathological_partition(
            labels, c.num_clients, seed=config.run.seed
        )
    if c.partition != "iid":
        # A typo must not silently train on an IID split — for the
        # literature protocol that would "validate" the non-IID anchor
        # against the wrong partition with plausible-looking numbers.
        raise ValueError(
            f"unknown data.partition {c.partition!r}; "
            "use iid | dirichlet | pathological"
        )
    return partition_lib.iid_partition(
        len(labels), c.num_clients, seed=config.run.seed
    )


def num_steps_for_config(config: ExperimentConfig, capacity: int) -> int:
    """Static per-round local step budget: explicit ``local_steps`` or
    ``local_epochs * ceil(capacity / batch_size)``."""
    c = config.fed
    if c.local_steps > 0:
        return c.local_steps
    steps_per_epoch = max(1, int(np.ceil(capacity / c.batch_size)))
    return c.local_epochs * steps_per_epoch


def local_trainer_for_config(
    config: ExperimentConfig,
    apply_fn: Callable,
    capacity: int,
    grad_sync_axes: tuple[str, ...] = (),
    lora_dense_ok: bool = False,
    param_axes: tuple[str, ...] = (),
) -> tuple[Callable, int]:
    """(local_update fn, num_steps) for one client round under ``config``.

    ``grad_sync_axes``: sequence-parallel mesh axes (fed/local.py).
    ``param_axes``: mesh axes the parameters are sharded over (fed/local.py).
    ``lora_dense_ok``: fleetsim prices LoRA factor frames but keeps its
    vmapped training dynamics dense by design (fleetsim/sim.py) — only
    it may build this dense trainer under ``lora_rank > 0``."""
    c = config.fed
    if c.lora_rank < 0:
        raise ValueError(f"lora_rank must be >= 0, got {c.lora_rank}")
    if c.lora_rank > 0 and not lora_dense_ok:
        # Adapter federation lives on the socket plane (comm/worker.py ->
        # lora_trainer_for_config); an in-process consumer reaching the
        # dense trainer with lora on would silently train the full model.
        raise ValueError(
            "lora_rank > 0 requires the socket federation plane "
            "(coordinate/worker); this in-process trainer would ignore "
            "the adapters and train dense"
        )
    if c.strategy == "scaffold" and c.local_optimizer != "sgd":
        raise ValueError(
            "scaffold's option-II variate refresh assumes plain SGD steps; "
            f"local_optimizer={c.local_optimizer!r} is unsupported"
        )
    if c.strategy == "fednova" and c.local_optimizer != "sgd":
        raise ValueError(
            "fednova's step coefficient a_i models SGD(+momentum) "
            f"dynamics; local_optimizer={c.local_optimizer!r} does not "
            "follow that geometric series and would be mis-normalized"
        )
    if c.strategy == "scaffold" and c.momentum != 0.0:
        # Option-II refresh c_i' = c_i - c + (w_g - w_l)/(K*lr) equals the
        # mean corrected gradient ONLY under vanilla SGD; momentum silently
        # biases the variates (and the default config carries momentum=0.9).
        raise ValueError(
            "scaffold requires momentum=0.0: the option-II control-variate "
            f"refresh is biased under momentum (got momentum={c.momentum})"
        )
    num_steps = num_steps_for_config(config, capacity)
    optimizer = local_lib.make_optimizer(c.lr, c.momentum, c.local_optimizer)
    is_moe = config.model.name.startswith("moe")
    update_fn = local_lib.make_local_update(
        apply_fn,
        optimizer,
        num_steps=num_steps,
        batch_size=c.batch_size,
        prox_mu=c.prox_mu if c.strategy == "fedprox" else 0.0,
        min_steps_fraction=c.straggler_min_fraction,
        grad_sync_axes=grad_sync_axes,
        scaffold=c.strategy == "scaffold",
        lr=c.lr,
        aux_loss_weight=config.model.moe_aux_weight if is_moe else 0.0,
        param_axes=param_axes,
    )
    return update_fn, num_steps


def lora_trainer_for_config(
    config: ExperimentConfig,
    apply_fn: Callable,
    capacity: int,
) -> tuple[Callable, int]:
    """(lora_update fn, num_steps) — factor-only twin of
    :func:`local_trainer_for_config`, built when ``fed.lora_rank > 0``.
    The strategy restriction (fedavg/fedprox only) is enforced by
    ``validate_robustness``; the trainer mirrors the dense step budget
    and optimizer so a lora run and its dense twin walk the same
    schedule."""
    c = config.fed
    num_steps = num_steps_for_config(config, capacity)
    optimizer = local_lib.make_optimizer(c.lr, c.momentum, c.local_optimizer)
    is_moe = config.model.name.startswith("moe")
    update_fn = local_lib.make_lora_local_update(
        apply_fn,
        optimizer,
        num_steps=num_steps,
        batch_size=c.batch_size,
        rank=c.lora_rank,
        alpha=c.lora_alpha,
        prox_mu=c.prox_mu if c.strategy == "fedprox" else 0.0,
        min_steps_fraction=c.straggler_min_fraction,
        aux_loss_weight=config.model.moe_aux_weight if is_moe else 0.0,
    )
    return update_fn, num_steps


# Tag folded into the experiment key for the A-factor init stream —
# disjoint from every prng.py tag so factor randomness never collides
# with data/local/dp key derivations.
_LORA_INIT_TAG = 0x10AA


def init_lora_factors(config: ExperimentConfig, params: Any) -> Any:
    """Seed-deterministic factor tree for ``params`` under ``config`` —
    the ONE derivation shared by coordinator, workers and tests, so every
    participant reconstructs the identical A basis from the config alone
    (B is zero everywhere; round 0 is bit-for-bit the base model)."""
    import jax

    from colearn_federated_learning_tpu.fed import lora
    from colearn_federated_learning_tpu.utils import prng

    key = jax.random.fold_in(
        prng.experiment_key(config.run.seed), _LORA_INIT_TAG)
    return lora.init_factors(
        params, config.fed.lora_rank, key=key,
        model_name=config.model.name)


def require_stateless_strategy(config: ExperimentConfig, where: str) -> None:
    """File/socket participants keep no cross-round client state, so the
    stateful SCAFFOLD strategy only runs in the on-device engine; FedNova
    is engine-only too — the wire/file folding is a plain weighted mean,
    which is exactly the step-count inconsistency FedNova corrects."""
    if config.fed.strategy == "scaffold":
        raise NotImplementedError(
            f"{where} does not support 'scaffold' (per-client control "
            "variates are engine-resident); use the on-device simulation "
            "or a stateless strategy"
        )
    if config.fed.strategy == "fednova":
        raise NotImplementedError(
            f"{where} does not support 'fednova' (its normalized "
            "aggregation is engine-resident); use the on-device "
            "simulation or fedavg/fedprox"
        )


def require_mean_aggregator(config: ExperimentConfig, where: str) -> None:
    """The file/socket aggregation planes fold updates incrementally
    (comm/aggregation.py, fed/offline.py) — coordinate-wise order
    statistics need ALL updates at once, so robust aggregators are
    engine-only.  Silently averaging when the config asks for 'median'
    would defeat the whole point; be loud instead."""
    if config.fed.aggregator != "mean":
        raise NotImplementedError(
            f"{where} does not support aggregator="
            f"{config.fed.aggregator!r} (robust aggregation is "
            "engine-only); use the on-device simulation or aggregator="
            "'mean'"
        )


def init_global_params(config: ExperimentConfig) -> Any:
    """Seed-deterministic global model init (shared by the file-based and
    socket-based federation entrypoints, so every participant derives the
    IDENTICAL starting point from the config alone)."""
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.data import registry as data_registry
    from colearn_federated_learning_tpu.models import registry as model_registry
    from colearn_federated_learning_tpu.utils import prng

    ds = data_registry.get_dataset(config.data.dataset, seed=config.run.seed,
                                   max_train=4 * config.fed.batch_size,
                                   max_test=1)
    model = model_registry.build_model(local_model_config(config.model))
    x = jnp.asarray(ds.x_train[: config.fed.batch_size])
    return model_registry.init_params(
        model, x, prng.init_key(prng.experiment_key(config.run.seed))
    )


def dp_effective_cohort(config: ExperimentConfig) -> int:
    """The cohort size the per-client DP noise is calibrated against
    (``σ·C/√B`` per update so the SUM of B updates carries std ``σ·C``).
    The ONE definition shared by the noise hook (finalize_client_delta)
    and every accountant that must match it (sync + async coordinators) —
    divergence would silently mis-report ε."""
    return max(config.fed.cohort_size or config.data.num_clients, 1)


def finalize_client_delta(
    config: ExperimentConfig, result, client_id: int, round_idx: int
) -> tuple[Any, float]:
    """Apply the config's on-update privacy hooks to one client's
    ``LocalResult`` and return ``(delta, aggregation_weight)`` — identical
    across the on-device engine's conventions: DP clipping+noise switches
    FedAvg to uniform weighting."""
    from colearn_federated_learning_tpu.privacy import dp as dp_lib
    from colearn_federated_learning_tpu.utils import prng

    delta = result.delta
    weight = float(result.num_examples)
    c = config.fed
    if c.dp_adaptive_clip:
        raise NotImplementedError(
            "dp_adaptive_clip is engine-only: the clip norm is cross-round "
            "server state the stateless file/socket participants don't "
            "carry; use the on-device simulation or a fixed dp_clip"
        )
    if c.dp_clip > 0.0:
        key = prng.experiment_key(config.run.seed)
        delta = dp_lib.clip_and_noise(
            delta, c.dp_clip, c.dp_noise_multiplier,
            dp_effective_cohort(config),
            prng.dp_key(key, client_id, round_idx),
        )
        weight = 1.0
    return delta, weight
