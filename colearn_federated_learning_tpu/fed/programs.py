"""Jit program construction for the federated engine (fed/engine.py).

Everything that BUILDS a compiled program lives here; the engine keeps
orchestration (data placement, host-side cohort bookkeeping, the public
API).  A program is built from a :class:`RoundPlan` (the static facts of
the federation, made and checked by :func:`plan_round`) and the client's
``local_update``: no learner, dataset or model is needed to build, trace
or test a round.

Shared interface of the two round-program builders: both return a jitted
function with the SAME signature

    round_fn(server_state, key, round_idx, x, y, counts, ids,
             sel, c_cohort, clip) -> (new_state, metrics, new_cohort_c)

- vmap path (``plan.mesh is None``): clients are a vmap axis; aggregation
  is a weighted tree-sum on one device.
- mesh path: clients are a manual shard_map axis over
  ``plan.mesh`` and aggregation lowers to ``jax.lax.psum`` over ICI
  (BASELINE.json north_star); a ``model`` (TP) axis, when present, is
  left to the automatic partitioner, and a ``seq`` axis carries the
  ring/Ulysses sequence-parallel collectives inside the model.

The cohort draw (``draw_cohort``), the per-cohort body (``cohort_step``)
and the round epilogue (``finish_round``) are shared verbatim between the
two paths — the mesh builder only adds the cross-device psums between
them.

A round's device time is told apart by four ``telemetry.device_scope``s,
in which everything a round program does lies: ``cohort`` (the draw, the
gather of the cohort's rows, its keys and budgets), ``local`` (the
clients' local updates; ``fed/local.py`` marks ``local.optimizer`` inside),
``aggregate`` (norms, clipping and noise, masks, the weighted or robust
sum, the collectives of the mesh path) and ``server`` (``finish_round``).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.fed import strategies
from colearn_federated_learning_tpu.fed.evaluation import per_label
from colearn_federated_learning_tpu.fed.robust import (
    AGGREGATORS,
    robust_aggregate,
)
from colearn_federated_learning_tpu.privacy import dp as dp_lib
from colearn_federated_learning_tpu.privacy import secure_agg as sa_lib
from colearn_federated_learning_tpu.utils import prng, pytrees
from colearn_federated_learning_tpu.utils.config import (
    ExperimentConfig,
    FedConfig,
)


@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """The static facts a round program closes over.  Made by
    :func:`plan_round`, which also refuses the combinations of options no
    program exists for."""

    fed: FedConfig
    num_clients: int            # array slots: ghost padding included
    real_num_clients: int
    cohort_size: int            # mesh-wide
    cohort_per_device: int      # == cohort_size off the mesh
    num_steps: int
    mesh: Optional[Mesh]
    client_axis: str
    clients_size: int           # devices along the client axis; 1 off the mesh
    seq_axis: Optional[str]     # None unless sequence-parallel
    dp_cohort: int              # contributors the DP noise is calibrated for
    dp_z: float = 0.0           # adaptive clipping: the update's multiplier
    dp_bit_noise: float = 0.0   # adaptive clipping: std of the bit query

    @property
    def scaffold(self) -> bool:
        return self.fed.strategy == "scaffold"

    @property
    def fednova(self) -> bool:
        return self.fed.strategy == "fednova"

    @property
    def robust(self) -> bool:
        return self.fed.aggregator != "mean"

    @property
    def adaptive_clip(self) -> bool:
        return self.fed.dp_adaptive_clip

    @property
    def track_norms(self) -> bool:
        """Per-client update norms (the quantity operators tune dp_clip
        against) are round telemetry ONLY for non-private plain runs —
        under DP the exact un-noised norms are an unaccounted release (the
        adaptive path pays for even a 1-bit norm query), and under
        secure-agg they are precisely what the masks exist to hide."""
        return not (self.fed.dp_clip > 0.0 or self.fed.secure_agg)

    @property
    def uniform_weights(self) -> bool:
        """SCAFFOLD averages uniformly over the sampled cohort (the variate
        algebra assumes it); DP/secure-agg and the robust statistics force
        uniform weights too."""
        return (self.fed.dp_clip > 0.0 or self.fed.secure_agg
                or self.scaffold or self.robust)

    @property
    def local_clients(self) -> int:
        """Client slots on one device of the client axis."""
        return self.num_clients // self.clients_size

    @property
    def x_spec(self) -> P:
        """Placement of the client-stacked examples on the mesh: clients
        over the client axis and, under SP, each client's token dim (last
        axis of the (clients, capacity, seq_len) block) over ``seq``."""
        if self.seq_axis is not None:
            return P(self.client_axis, None, self.seq_axis)
        return P(self.client_axis)


def plan_round(config: ExperimentConfig, *, num_clients: int,
               real_num_clients: int, num_steps: int,
               mesh: Optional[Mesh] = None, stacklevel: int = 2) -> RoundPlan:
    """The plan of ``config``'s round over ``num_clients`` client slots
    (``real_num_clients`` of them not ghost padding) of ``num_steps`` local
    steps each, on ``mesh`` or one device.  Every check on the federation's
    options is made here; ``stacklevel`` is the frame the cohort warning is
    attributed to (2: the caller)."""
    c, run = config.fed, config.run
    shape = mesh.shape if mesh is not None else {}
    clients_size = shape.get(run.mesh_axis, 1)
    requested = min(c.cohort_size or num_clients, num_clients)
    # The per-device cohort must be equal and static.
    cohort_per_device = (requested if mesh is None
                         else max(1, requested // clients_size))
    cohort_size = cohort_per_device * clients_size
    plan = RoundPlan(
        fed=c, num_clients=num_clients, real_num_clients=real_num_clients,
        cohort_size=cohort_size, cohort_per_device=cohort_per_device,
        num_steps=num_steps, mesh=mesh, client_axis=run.mesh_axis,
        clients_size=clients_size,
        seq_axis=run.seq_axis if shape.get(run.seq_axis, 1) > 1 else None,
        # DP noise accounting divides by the number of REAL clients expected
        # to contribute (ghost padding never contributes).  If stragglers
        # drop mid-round the realized central noise is below nominal — a
        # known property of DP-FedAvg with dropouts; see privacy/dp.py.
        dp_cohort=min(cohort_size, real_num_clients),
    )
    _check_options(plan, tp_size=shape.get(run.tp_axis, 1))
    if cohort_size != requested:
        warnings.warn(
            f"cohort_size={requested} is not a multiple of the "
            f"{clients_size}-way client axis; using {cohort_size} "
            f"({cohort_per_device}/device)",
            stacklevel=stacklevel,
        )
    _check_trim(plan)
    if not plan.adaptive_clip:
        return plan
    # Adaptive clipping (privacy/dp.py, quantile tracking): the clip norm
    # is a DEVICE scalar threaded operand -> metric through the round
    # program, so back-to-back rounds adapt it with no host sync.
    if c.dp_clip <= 0.0:
        raise ValueError(
            "dp_adaptive_clip needs dp_clip > 0 as the initial norm"
        )
    if c.dp_noise_multiplier <= 0.0:
        return plan
    bit_noise = c.dp_bit_noise or max(plan.dp_cohort / 20.0, 1.0)
    # The bit query spends part of the budget; the update noise is inflated
    # so the JOINT per-round mechanism still costs the configured z — the
    # engine's accountant stays valid as-is.
    return dataclasses.replace(
        plan, dp_bit_noise=bit_noise,
        dp_z=dp_lib.adaptive_noise_multiplier(c.dp_noise_multiplier,
                                              bit_noise))


def _check_options(plan: RoundPlan, tp_size: int) -> None:
    """Refuse what no round program exists for."""
    c = plan.fed
    if c.secure_agg and c.secure_agg_neighbors and (
        c.secure_agg_neighbors % 2 or c.secure_agg_neighbors < 2
    ):
        raise ValueError(
            "secure_agg_neighbors must be an even integer >= 2, got "
            f"{c.secure_agg_neighbors}"
        )
    if c.secure_agg and not 0.0 < c.secure_agg_threshold <= 1.0:
        raise ValueError(
            "secure_agg_threshold must be in (0, 1], got "
            f"{c.secure_agg_threshold}"
        )
    if plan.scaffold and (c.secure_agg or c.dp_clip > 0.0):
        raise ValueError(
            "scaffold is incompatible with secure_agg/dp hooks: the "
            "control-variate deltas are a second payload the masks and "
            "noise calibration do not cover"
        )
    if plan.scaffold and tp_size > 1:
        raise ValueError(
            "scaffold with a model (TP) axis is unsupported: the "
            "host-resident variate store is unsharded and the per-round "
            "gather/scatter would funnel TP shards through one host"
        )
    # Byzantine-robust aggregation (fed/robust.py).
    if c.aggregator not in AGGREGATORS:
        raise ValueError(
            f"unknown aggregator {c.aggregator!r}; use {AGGREGATORS}"
        )
    if not plan.robust:
        return
    if not 0.0 <= c.trim_fraction < 0.5:
        raise ValueError(
            "trim_fraction must be in [0, 0.5), got "
            f"{c.trim_fraction}"
        )
    if c.secure_agg:
        raise ValueError(
            "robust aggregators need the individual updates; "
            "secure-agg masks only cancel in a plain sum"
        )
    if plan.scaffold:
        raise ValueError(
            "scaffold assumes mean aggregation of its control "
            "variates; use aggregator='mean'"
        )
    if c.dp_noise_multiplier > 0.0:
        raise ValueError(
            "robust aggregation of noised updates is not the "
            "Gaussian mechanism the RDP accountant models; use "
            "dp_clip alone (norm bounding) with robust aggregators"
        )


def _check_trim(plan: RoundPlan) -> None:
    """floor(trim · cohort) == 0 trims/excludes nothing — the "robust"
    aggregate would silently be the plain mean while still paying uniform
    weights and the secure-agg/DP bans."""
    c = plan.fed
    if (c.aggregator not in ("trimmed_mean", "krum")
            or int(c.trim_fraction * plan.cohort_size + 1e-4) >= 1):
        return
    if plan.cohort_size < 3:
        # Any fraction satisfying floor(trim·cohort) >= 1 here would breach
        # the < 0.5 cap: no valid value exists.
        raise ValueError(
            f"aggregator={c.aggregator!r} needs a cohort of at "
            f"least 3 (got {plan.cohort_size}); use "
            "aggregator='median'"
        )
    what = ("trims zero clients" if c.aggregator == "trimmed_mean"
            else "assumes zero Byzantine clients (f = 0)")
    # Round the suggestion UP so following it actually passes.
    ok_frac = math.ceil(1e6 / plan.cohort_size) / 1e6
    raise ValueError(
        f"trim_fraction={c.trim_fraction} {what} at "
        f"cohort_size={plan.cohort_size}; raise it to at least "
        f"{ok_frac:.6f} (or use aggregator='median')"
    )


def rank_cohort(skey, counts, k):
    """Uniform sample of ``k`` clients WITHOUT replacement among real
    clients: ghosts (count 0) are pushed to the end of the ranking and only
    picked if the cohort exceeds real clients.  Pure jnp, so it runs traced
    and eagerly alike (``draw_cohort``)."""
    scores = jax.random.uniform(skey, counts.shape)
    scores = scores + (counts == 0) * 1e3
    return jnp.argsort(scores)[:k]


def draw_cohort(plan: RoundPlan, key, round_idx, counts, device=None):
    """The round's cohort as slot indices into ``counts``: the whole
    population, or ``rank_cohort`` under the round's sampling key.  On a
    mesh each device draws its own slice of the cohort among ITS clients
    (interleaved placement spreads the real ones evenly): ``counts`` is
    that device's block and ``device`` its index along the client axis,
    folded into the key.  The ONE draw: the round programs call it traced,
    the engine eagerly on the host (SCAFFOLD must know the cohort before
    dispatch to gather its variate rows) — same key, same ranking, same
    cohort."""
    skey = prng.sampling_key(key, round_idx)
    if device is None:
        population, k = plan.num_clients, plan.cohort_size
    else:
        skey = jax.random.fold_in(skey, device)
        population, k = plan.local_clients, plan.cohort_per_device
    if k < population:
        return rank_cohort(skey, counts, k)
    return jnp.arange(population)


def manual_axes(plan: RoundPlan) -> frozenset:
    """Mesh axes the round shard_map is MANUAL over: clients (+ seq
    under SP).  A ``model`` (TP) axis stays out of the set, so the
    automatic partitioner handles it — params arrive sharded over it
    (parallel/tp.py) and XLA inserts the tensor-parallel collectives."""
    axes = {plan.client_axis}
    if plan.seq_axis is not None:
        axes.add(plan.seq_axis)
    return frozenset(axes)


def donate_argnums(plan: RoundPlan) -> tuple[int, ...]:
    """Donate the consumed round state (server_state, cohort variate
    block) so XLA reuses their HBM in place — matters for big models.
    CPU ignores donation with a warning, so skip."""
    devs = plan.mesh.devices.flat if plan.mesh is not None else jax.devices()
    first = next(iter(devs))
    return () if first.platform == "cpu" else (0, 8)


def cohort_step(plan: RoundPlan, local_update: Callable, params, local_ids,
                global_ids, mask_cohort_ids, x, y, counts, key, round_idx,
                control=None, c_blk=None, clip=None):
    """Shared per-cohort logic: local training + privacy + weighting.

    ``local_ids`` index into the (possibly per-device) ``x/y/counts``
    blocks; ``global_ids`` are the mesh-wide client identities used for
    PRNG derivation, so results are bit-identical regardless of how
    clients are placed on devices.  ``mask_cohort_ids`` is the FULL
    round cohort (all devices) that secure-agg masks pair against.
    ``control`` / ``c_blk`` are the scaffold global variate and the
    COHORT-ALIGNED block of per-client variates (one row per cohort
    slot, gathered host-side from the full store before the call).
    Returns (weighted_delta_sum, total_weight, metrics, scaffold_extras)
    — the caller finishes aggregation either locally (vmap path) or
    with a psum (shard_map path); ``scaffold_extras`` is None or
    ``(delta_c_uniform_sum, n_contributors, updated_cohort_block)``.
    """
    with telemetry.device_scope("cohort"):
        rows = _cohort_rows(plan, local_ids, global_ids, x, y, counts, key,
                            round_idx)
    with telemetry.device_scope("local"):
        if plan.scaffold:
            sres = jax.vmap(
                local_update,
                in_axes=(None, 0, 0, 0, 0, 0, 0, None, None),
            )(params, *rows[:-1], c_blk, control, rows[-1])
            results = sres.result
        else:
            sres = None
            results = jax.vmap(
                local_update, in_axes=(None, 0, 0, 0, 0, 0, None)
            )(params, *rows)
    with telemetry.device_scope("aggregate"):
        return _aggregate(plan, results, sres, c_blk, global_ids,
                          mask_cohort_ids, key, round_idx, clip)


def _cohort_rows(plan: RoundPlan, local_ids, global_ids, x, y, counts, key,
                 round_idx):
    """What ``local_update`` takes per cohort slot: the cohort's rows of
    ``x``, ``y`` and ``counts``, its keys and step budgets, and the
    round's learning-rate factor."""
    c = plan.fed
    cx = jnp.take(x, local_ids, axis=0)
    cy = jnp.take(y, local_ids, axis=0)
    ccounts = jnp.take(counts, local_ids, axis=0)

    # Per-(client, round) keys: placement-independent determinism.
    keys = jax.vmap(lambda i: prng.client_round_key(key, i, round_idx))(global_ids)

    # Straggler simulation: each cohort slot draws a per-CLIENT budget
    # (keyed on global id, so placement-independent).
    if c.straggler_prob > 0.0:
        skey = prng.straggler_key(key, round_idx)

        def budget_for(i):
            k = jax.random.fold_in(skey, i)
            slow = jax.random.bernoulli(k, c.straggler_prob)
            frac = jax.random.uniform(jax.random.fold_in(k, 1))
            return jnp.where(
                slow, (frac * plan.num_steps).astype(jnp.int32),
                plan.num_steps
            )

        budgets = jax.vmap(budget_for)(global_ids)
    else:
        budgets = jnp.full((plan.cohort_per_device,), plan.num_steps,
                           jnp.int32)

    # Round-level client-lr schedule factor, computed in-graph from
    # the round operand (no retrace, no host sync).
    lr_scale = strategies.lr_scale_for_round(c, round_idx)
    return cx, cy, ccounts, keys, budgets, lr_scale


def _aggregate(plan: RoundPlan, results, sres, c_i, global_ids,
               mask_cohort_ids, key, round_idx, clip):
    """The cohort's results (``sres``: SCAFFOLD's, with its variates ``c_i``
    one row per cohort slot) as ``cohort_step`` returns them."""
    c = plan.fed
    deltas = results.delta
    completed = results.completed
    nova_a = None
    if plan.fednova:
        # FedNova (Wang et al., pattern only): normalize each delta by
        # its effective local-step coefficient a_i, so heterogeneous
        # step counts (straggler budgets!) stop biasing the objective;
        # the round epilogue rescales the mean by the weighted mean a.
        m = c.momentum
        tau = jnp.maximum(results.steps_run, 1.0)
        if m > 0.0:
            nova_a = (tau - m * (1.0 - m ** tau) / (1.0 - m)) / (1.0 - m)
        else:
            nova_a = tau
        deltas = jax.vmap(
            lambda d, a: pytrees.tree_scale(d, 1.0 / a)
        )(deltas, nova_a)
    if plan.track_norms:
        norms = jax.vmap(pytrees.tree_global_norm)(deltas)

    bits = None
    if c.dp_clip > 0.0:
        dp_keys = jax.vmap(lambda i: prng.dp_key(key, i, round_idx))(global_ids)
        if plan.adaptive_clip:
            # Traced clip scalar + per-client quantile bit (pre-clip
            # norm <= clip), update noise at the inflated multiplier.
            deltas, bits = jax.vmap(
                lambda d, k: dp_lib.clip_and_noise_with_bit(
                    d, clip, plan.dp_z, plan.dp_cohort, k
                )
            )(deltas, dp_keys)
        else:
            deltas = jax.vmap(
                lambda d, k: dp_lib.clip_and_noise(
                    d, c.dp_clip, c.dp_noise_multiplier, plan.dp_cohort, k
                )
            )(deltas, dp_keys)

    nonghost = (results.num_examples > 0)
    # The ONE contributor mask (real, non-straggler) every aggregation
    # branch and metric below derives from.
    contrib = completed & nonghost
    if plan.uniform_weights:
        weights = contrib.astype(jnp.float32)
    else:
        weights = results.num_examples.astype(jnp.float32) * contrib

    sa_bit_sum = None
    if c.secure_agg:
        # Clients pre-scale by their weight, then add pairwise masks;
        # masks cancel in the plain SUM over the cohort.  Masks pair
        # GLOBAL ids, so cancellation holds across devices too (the
        # final sum is the psum over the mesh).
        wdeltas = jax.vmap(lambda d, w: pytrees.tree_scale(d, w))(deltas, weights)
        # The per-round pairing graph (ring permutation or complete
        # graph) is computed ONCE here, not per vmap lane — each lane
        # then does only O(partners) PRG work.
        partners = sa_lib.partner_table(
            key, global_ids, mask_cohort_ids, round_idx,
            neighbors=c.secure_agg_neighbors,
        )
        masked = jax.vmap(
            lambda d, i, prt: sa_lib.mask_update(d, key, i, prt,
                                                 round_idx)
        )(wdeltas, global_ids, partners)
        wsum = jax.tree.map(lambda l: jnp.sum(l, axis=0), masked)
        if bits is not None:
            # Adaptive clipping under secure-agg: the quantile bit is a
            # second payload — mask it on its own pair stream so only
            # the cohort SUM is visible, like the deltas (the
            # contribution weighting is folded in pre-mask).
            # std ≫ 1: a unit-scale mask on a {0,1} payload would leak
            # the bit with constant statistical advantage; at 1e3 the
            # float32 cancellation residual (~1e-7·std·√cohort) is
            # still far below the O(cohort) bit sum.
            masked_bits = jax.vmap(
                lambda b, i, prt: sa_lib.mask_scalar(b, key, i, prt,
                                                     round_idx, std=1e3)
            )(bits * contrib.astype(jnp.float32), global_ids, partners)
            sa_bit_sum = jnp.sum(masked_bits)
    elif plan.robust:
        # Coordinate-wise robust statistic over the FULL cohort
        # (fed/robust.py).  Order statistics are not psum-decomposable,
        # so on a mesh the stacked deltas are all-gathered over the
        # client axis first and the aggregate comes out replicated —
        # the round epilogue uses it directly (no psum, no division).
        if plan.mesh is not None:
            ax = plan.client_axis
            all_deltas = jax.tree.map(
                lambda l: jax.lax.all_gather(l, ax, axis=0, tiled=True),
                deltas,
            )
            all_contrib = jax.lax.all_gather(contrib, ax, axis=0,
                                             tiled=True)
        else:
            all_deltas, all_contrib = deltas, contrib
        wsum = robust_aggregate(all_deltas, all_contrib,
                                c.aggregator, c.trim_fraction)
    else:
        wsum = pytrees.tree_weighted_sum(deltas, weights)

    total_w = jnp.sum(weights)
    loss_sum = jnp.sum(results.mean_loss * weights)
    # "completed" reports real contributors only (ghost padding slots
    # always finish their budget but never contribute).
    n_completed = jnp.sum(contrib.astype(jnp.int32))
    # Quantile-bit sum over CONTRIBUTORS (the clip adapts to the norms
    # that actually entered the aggregate).  Under secure-agg the
    # masked sum computed above stands in (cancellation ⇒ same value
    # up to float32 residual).
    if sa_bit_sum is not None:
        bit_sum = sa_bit_sum
    elif bits is not None:
        bit_sum = jnp.sum(bits * contrib.astype(jnp.float32))
    else:
        bit_sum = jnp.zeros((), jnp.float32)
    if plan.track_norms:
        cf = contrib.astype(jnp.float32)
        norm_sum = jnp.sum(norms * cf)
        norm_max = jnp.max(norms * cf)
    else:
        norm_sum = norm_max = jnp.zeros((), jnp.float32)
    # FedNova: weighted sum of the a_i coefficients — the epilogue's
    # mean rescale factor is nova_sum / total_w.
    nova_sum = (
        jnp.sum(weights * nova_a)
        if nova_a is not None else jnp.zeros((), jnp.float32)
    )

    extras = None
    if plan.scaffold:
        uw = contrib.astype(jnp.float32)
        dc_sum = pytrees.tree_weighted_sum(sres.delta_c, uw)
        # Refresh only contributors' variates; non-contributor rows keep
        # their old values.  The caller scatters this cohort block back
        # into the host-resident full store.
        c_masked = jax.tree.map(
            lambda new, old: jnp.where(
                contrib.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
            ),
            sres.c_new, c_i,
        )
        extras = (dc_sum, n_completed.astype(jnp.float32), c_masked)
    return (wsum, total_w,
            (loss_sum, n_completed, bit_sum, norm_sum, norm_max,
             nova_sum), extras)


def finish_round(plan: RoundPlan, server_state, wsum, total_w, stats,
                 extras, clip, key, round_idx):
    """Shared round epilogue (vmap and shard_map paths): mean delta,
    server update, metrics.  ``stats`` and ``extras`` are what
    ``cohort_step`` returned, summed over the mesh where there is one.
    Zero contributors (all stragglers) → no-op update; the explicit gate
    matters under secure_agg, where wsum is not exactly zero but the
    float32 mask-cancellation residual."""
    with telemetry.device_scope("server"):
        return _finish_round(plan, server_state, wsum, total_w, stats,
                             extras, clip, key, round_idx)


def _finish_round(plan: RoundPlan, server_state, wsum, total_w, stats,
                  extras, clip, key, round_idx):
    loss_sum, n_comp, bit_sum, norm_sum, norm_max, nova_sum = stats
    denom = jnp.where(total_w > 0, total_w, 1.0)
    if plan.robust:
        # wsum IS the robust aggregate (zero when nobody contributed);
        # total_w only normalizes the loss metric below.
        mean_delta = wsum
    else:
        mean_delta = pytrees.tree_scale(
            wsum, jnp.where(total_w > 0, 1.0 / denom, 0.0)
        )
    if plan.fednova:
        # Rescale the mean of NORMALIZED deltas by the weighted-mean
        # step coefficient (tau_eff), completing d = tau_eff * mean.
        mean_delta = pytrees.tree_scale(mean_delta, nova_sum / denom)
    mean_delta_c = participation = None
    if plan.scaffold:
        dc_sum, n_contrib, _ = extras
        safe_n = jnp.maximum(n_contrib, 1.0)
        mean_delta_c = pytrees.tree_scale(
            dc_sum, jnp.where(n_contrib > 0, 1.0 / safe_n, 0.0)
        )
        participation = n_contrib / float(plan.real_num_clients)
    new_state = strategies.server_update(server_state, mean_delta,
                                         plan.fed,
                                         mean_delta_c=mean_delta_c,
                                         participation=participation)
    metrics = {
        "train_loss": loss_sum / denom,
        "completed": n_comp,
        "total_weight": total_w,
    }
    if plan.track_norms:
        safe_n = jnp.maximum(n_comp.astype(jnp.float32), 1.0)
        metrics["delta_norm_mean"] = norm_sum / safe_n
        metrics["delta_norm_max"] = norm_max
    if plan.adaptive_clip:
        # Noised quantile fraction -> geometric clip step.  In the
        # shard_map path this runs replicated AFTER the psums: every
        # device derives the identical noise from the shared key, so
        # the updated clip stays replicated.
        c = plan.fed
        bnoise = (
            plan.dp_bit_noise
            * jax.random.normal(prng.clip_bit_key(key, round_idx), ())
            if plan.dp_bit_noise > 0.0 else 0.0
        )
        frac = jnp.clip(
            (bit_sum + bnoise)
            / jnp.maximum(n_comp.astype(jnp.float32), 1.0),
            0.0, 1.0,
        )
        new_clip = dp_lib.adaptive_clip_update(
            clip, frac, c.dp_target_quantile, c.dp_clip_lr
        )
        # A zero-contributor round (all stragglers) carries no norm
        # evidence: freeze the clip like the server update freezes.
        new_clip = jnp.where(n_comp > 0, new_clip, clip)
        metrics["dp_clip"] = jnp.maximum(new_clip, 1e-6)
        metrics["dp_bit_frac"] = frac
    return new_state, metrics


# The two builders differ in the sampling key (folded with the device's
# index on a mesh) and the collectives, and in nothing else.  They stay
# two functions because readers of a device trace tell the programs by
# the jitted functions' names, ``round_fn`` and ``body`` (ROADMAP D16).
def _build_vmap_round(plan: RoundPlan, local_update: Callable):
    """Single-device path: clients are a vmap axis inside cohort_step."""

    def round_fn(server_state, key, round_idx, x, y, counts, ids,
                 sel_in, c_cohort, clip_in):
        # SCAFFOLD's cohort was drawn on the host (so its variate rows
        # could be gathered) and arrives as an operand.
        with telemetry.device_scope("cohort"):
            sel = sel_in if plan.scaffold else draw_cohort(
                plan, key, round_idx, counts)
            cohort_global = jnp.take(ids, sel)
        wsum, total_w, stats, extras = cohort_step(
            plan, local_update, server_state.params, sel, cohort_global,
            cohort_global, x, y, counts, key, round_idx,
            control=server_state.control, c_blk=c_cohort,
            clip=clip_in,
        )
        new_state, metrics = finish_round(
            plan, server_state, wsum, total_w, stats, extras, clip_in,
            key, round_idx)
        return new_state, metrics, extras[2] if plan.scaffold else None

    return jax.jit(round_fn, donate_argnums=donate_argnums(plan))


def _build_mesh_round(plan: RoundPlan, local_update: Callable,
                      state_shardings):
    """Multi-chip path: shard_map over the client axis (and, under SP,
    the sequence axis — every collective below names ONLY the client
    axis, so the ring collectives inside the model stay on ``seq``)."""
    ax = plan.client_axis

    def body(server_state, key, round_idx, x_blk, y_blk, counts_blk,
             ids_blk, sel_blk, c_blk, clip_in):
        with telemetry.device_scope("cohort"):
            sel = sel_blk if plan.scaffold else draw_cohort(
                plan, key, round_idx, counts_blk,
                device=jax.lax.axis_index(ax))
            cohort_global = jnp.take(ids_blk, sel)
            # Secure-agg masks pair against the FULL mesh-wide cohort: a
            # cheap all_gather of the (cohort_per_device,) id vectors.
            mask_cohort = jax.lax.all_gather(cohort_global, ax).reshape(-1)
        wsum, total_w, stats, extras = cohort_step(
            plan, local_update, server_state.params, sel, cohort_global,
            mask_cohort, x_blk, y_blk, counts_blk, key, round_idx,
            control=server_state.control, c_blk=c_blk, clip=clip_in,
        )
        loss_sum, n_comp, bit_sum, norm_sum, norm_max, nova_sum = stats
        with telemetry.device_scope("aggregate"):
            # FedAvg across the pod: one psum over ICI per leaf.  (Robust
            # aggregates are already global+replicated — no psum.)
            if not plan.robust:
                wsum = jax.tree.map(lambda l: jax.lax.psum(l, ax), wsum)
            total_w = jax.lax.psum(total_w, ax)
            stats = (jax.lax.psum(loss_sum, ax), jax.lax.psum(n_comp, ax),
                     jax.lax.psum(bit_sum, ax), jax.lax.psum(norm_sum, ax),
                     jax.lax.pmax(norm_max, ax), jax.lax.psum(nova_sum, ax))
            if plan.scaffold:
                dc_sum, n_contrib, new_c = extras
                extras = (
                    jax.tree.map(lambda l: jax.lax.psum(l, ax), dc_sum),
                    jax.lax.psum(n_contrib, ax), new_c)
        new_state, metrics = finish_round(
            plan, server_state, wsum, total_w, stats, extras, clip_in,
            key, round_idx)
        return new_state, metrics, extras[2] if plan.scaffold else None

    c_spec = P(ax) if plan.scaffold else P()
    sel_spec = P(ax) if plan.scaffold else P()
    sharded = jax.shard_map(
        body,
        mesh=plan.mesh,
        in_specs=(P(), P(), P(), plan.x_spec, P(ax), P(ax), P(ax), sel_spec,
                  c_spec, P()),
        out_specs=(P(), P(), c_spec),
        axis_names=manual_axes(plan),
        check_vma=False,
    )
    # The state goes back out placed as it came in.  Left to itself the
    # partitioner may lay a replicated leaf over the auto ``model`` axis
    # (MoE routers), and jit would compile the whole program again for
    # the second round's new argument placement.
    return jax.jit(sharded, donate_argnums=donate_argnums(plan),
                   out_shardings=(state_shardings, None, None))


def build_round_fn(plan: RoundPlan, local_update: Callable,
                   state_shardings=None):
    """The round program of ``plan`` around ``local_update``: dispatch on
    mesh presence; both builders honor the shared signature documented in
    the module docstring.  ``state_shardings``: the server state's, leaf
    for leaf, on a mesh (None: left to the partitioner)."""
    if plan.mesh is None:
        return _build_vmap_round(plan, local_update)
    return _build_mesh_round(plan, local_update, state_shardings)


# ---------------------------------------------------------------------
# per-client programs (eval / personalization / similarity)
# ---------------------------------------------------------------------
def _over_clients(plan: RoundPlan, one_client: Callable, n_per_client: int,
                  n_out: int):
    """``one_client(params, x, *per_client)`` vmapped over the clients and,
    on a mesh, sharded over the client axis: ``n_per_client`` client-stacked
    arguments after ``x``, ``n_out`` client-stacked results."""
    vmapped = jax.vmap(one_client,
                       in_axes=(None, 0) + (0,) * n_per_client)
    if plan.mesh is None:
        return jax.jit(vmapped)
    ax = plan.client_axis
    return jax.jit(jax.shard_map(
        vmapped, mesh=plan.mesh,
        in_specs=(P(), plan.x_spec) + (P(ax),) * n_per_client,
        out_specs=(P(ax),) * n_out,
        axis_names=manual_axes(plan),
        check_vma=False,
    ))


def _chunks(cx, cy, capacity: int, batch: int):
    """One client's shard padded to whole chunks of ``batch`` rows, for a
    scan that bounds activation memory: the chunked examples and labels,
    and each chunk's first row."""
    n_chunks = int(np.ceil(capacity / batch))
    pad = n_chunks * batch - capacity
    if pad:
        cx = jnp.concatenate([cx, jnp.zeros((pad,) + cx.shape[1:], cx.dtype)])
        cy = jnp.concatenate([cy, jnp.zeros((pad,) + cy.shape[1:], cy.dtype)])
    return (cx.reshape((n_chunks, batch) + cx.shape[1:]),
            cy.reshape((n_chunks, batch) + cy.shape[1:]),
            jnp.arange(n_chunks) * batch)


def build_client_eval_fn(plan: RoundPlan, apply_fn: Callable, capacity: int,
                         batch: int):
    """Per-client (loss, acc) of the CURRENT global params on each
    client's own shard of ``capacity`` rows — vmapped, sharded over the
    client axis on a mesh, scanned in chunks of ``batch`` rows.  Under SP
    the shard data arrives sequence-sharded, so ``apply_fn`` must be the
    ring-attention (SP-aware) module's, not the dense twin's."""

    def one_client(params, cx, cy, count):
        def step(carry, inp):
            x_, y_, b = inp
            logits = apply_fn({"params": params}, x_, train=False)
            ce = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(ce, y_[..., None], axis=-1)[..., 0]
            correct = (jnp.argmax(logits, axis=-1) == y_).astype(jnp.float32)
            # Only rows < count score.
            m = per_label(
                ((b + jnp.arange(batch)) < count).astype(jnp.float32), nll)
            l, a, n = carry
            return (l + jnp.sum(nll * m), a + jnp.sum(correct * m),
                    n + jnp.sum(m)), None

        (l, a, n), _ = jax.lax.scan(step, (0.0, 0.0, 0.0),
                                    _chunks(cx, cy, capacity, batch))
        n = jnp.maximum(n, 1.0)
        return l / n, a / n

    return _over_clients(plan, one_client, n_per_client=2, n_out=2)


def build_personalized_eval_fn(plan: RoundPlan, apply_fn: Callable,
                               fine_tune: Callable, capacity: int, batch: int,
                               key, steps: int):
    """Fine-tune-then-eval probe: ``steps`` local steps of ``fine_tune``
    (a ``local_update``) on the first half of each client's shard, score
    global vs personalized params on the second half
    (fed/engine.evaluate_personalized).  ``key``: the experiment's."""
    budget = jnp.asarray(steps, jnp.int32)

    def score(params, cx, cy, lo, hi):
        """Mean accuracy over shard rows [lo, hi), scanned in
        batch-sized chunks (same scheme as build_client_eval_fn)."""

        def chunk(carry, inp):
            x_, y_, b = inp
            logits = apply_fn({"params": params}, x_, train=False)
            correct = (jnp.argmax(logits, axis=-1) == y_).astype(jnp.float32)
            rows = b + jnp.arange(batch)
            m = per_label(
                ((rows >= lo) & (rows < hi)).astype(jnp.float32), correct)
            a, n = carry
            return (a + jnp.sum(correct * m), n + jnp.sum(m)), None

        (a, n), _ = jax.lax.scan(chunk, (0.0, 0.0),
                                 _chunks(cx, cy, capacity, batch))
        return a / jnp.maximum(n, 1.0)

    def one_client(params, cx, cy, count, gid):
        n_ft = count // 2                       # fine-tune half
        n_eval = jnp.where(count >= 2, count - n_ft, 0)
        # Purpose-distinct key: round index past any training round.
        ckey = prng.client_round_key(
            key, gid, jnp.asarray(1 << 24, jnp.int32)
        )
        res = fine_tune(params, cx, cy, jnp.maximum(n_ft, 1), ckey, budget)
        pers = pytrees.tree_add(params, res.delta)
        g_acc = score(params, cx, cy, n_ft, count)
        p_acc = score(pers, cx, cy, n_ft, count)
        return g_acc, p_acc, n_eval

    return _over_clients(plan, one_client, n_per_client=3, n_out=3)


def build_similarity_fn(plan: RoundPlan, local_update: Callable, steps: int):
    """(N, N) cosine-similarity program over every client's local update
    (clustered FL signal; fed/engine.client_update_similarity documents
    the mesh strategy — all_gather the normalized deltas, per-device gram
    strips on the MXU)."""
    budget = jnp.asarray(min(steps, plan.num_steps), jnp.int32)

    def flat_norm_deltas(params, x, y, counts, ids, key, n_rows):
        keys = jax.vmap(
            lambda i: prng.client_round_key(key, i, 1 << 23)
        )(ids)
        budgets = jnp.full((n_rows,), budget, jnp.int32)
        res = jax.vmap(local_update,
                       in_axes=(None, 0, 0, 0, 0, 0))(
            params, x, y, counts, keys, budgets
        )
        X = jnp.concatenate(
            [l.reshape(n_rows, -1).astype(jnp.float32)
             for l in jax.tree.leaves(res.delta)], axis=1,
        )
        return X / jnp.maximum(
            jnp.linalg.norm(X, axis=1, keepdims=True), 1e-12
        )

    if plan.mesh is None:
        def sim(params, x, y, counts, ids, key):
            Xn = flat_norm_deltas(params, x, y, counts, ids, key,
                                  plan.num_clients)
            return Xn @ Xn.T

        return jax.jit(sim)

    ax = plan.client_axis

    def sim_body(params, x_blk, y_blk, counts_blk, ids_blk, key):
        Xn = flat_norm_deltas(params, x_blk, y_blk, counts_blk,
                              ids_blk, key, plan.local_clients)
        x_all = jax.lax.all_gather(Xn, ax)
        x_all = x_all.reshape(-1, Xn.shape[1])     # (N, P)
        return Xn @ x_all.T                        # (N/D, N)

    return jax.jit(jax.shard_map(
        sim_body,
        mesh=plan.mesh,
        in_specs=(P(), plan.x_spec, P(ax), P(ax), P(ax), P()),
        out_specs=P(ax, None),
        axis_names=manual_axes(plan),
        check_vma=False,
    ))
