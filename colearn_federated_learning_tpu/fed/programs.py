"""Jit program construction for the federated engine (fed/engine.py).

Everything that BUILDS a compiled program lives here; the engine keeps
orchestration (data placement, host-side cohort bookkeeping, the public
API).  Extracted from the 1,400-line engine in round 5 (VERDICT r4 weak
#6) with no behavior change — the functions take the learner (``ln``)
and read the same attributes the former methods read off ``self``.

Shared interface of the two round-program builders: both return a jitted
function with the SAME signature

    round_fn(server_state, key, round_idx, x, y, counts, ids,
             sel, c_cohort, clip) -> (new_state, metrics, new_cohort_c)

- vmap path (``ln.mesh is None``): clients are a vmap axis; aggregation
  is a weighted tree-sum on one device.
- mesh path: clients are a manual shard_map axis over
  ``ln.mesh`` and aggregation lowers to ``jax.lax.psum`` over ICI
  (BASELINE.json north_star); a ``model`` (TP) axis, when present, is
  left to the automatic partitioner, and a ``seq`` axis carries the
  ring/Ulysses sequence-parallel collectives inside the model.

The per-cohort body (``cohort_step``) and the round epilogue
(``finish_round``) are shared verbatim between the two paths — the mesh
builder only adds the cross-device psums between them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from colearn_federated_learning_tpu.fed import strategies
from colearn_federated_learning_tpu.fed.evaluation import eval_rows, per_label
from colearn_federated_learning_tpu.privacy import dp as dp_lib
from colearn_federated_learning_tpu.privacy import secure_agg as sa_lib
from colearn_federated_learning_tpu.utils import prng, pytrees


def rank_cohort(skey, counts, k):
    """Uniform sample of ``k`` clients WITHOUT replacement among real
    clients: ghosts (count 0) are pushed to the end of the ranking and only
    picked if the cohort exceeds real clients.  Pure jnp — the SAME function
    runs traced inside the round program (fedavg paths) and eagerly on host
    (the scaffold path, which must know the cohort before dispatch to gather
    its variate rows; fleetsim's host sampler too); any edit applies to
    all of them.  Public: engine.py and fleetsim/sim.py import it."""
    scores = jax.random.uniform(skey, counts.shape)
    scores = scores + (counts == 0) * 1e3
    return jnp.argsort(scores)[:k]


# Back-compat alias for the historical private name.
_rank_cohort = rank_cohort


def manual_axes(ln) -> frozenset:
    """Mesh axes the round shard_map is MANUAL over: clients (+ seq
    under SP).  A ``model`` (TP) axis stays out of the set, so the
    automatic partitioner handles it — params arrive sharded over it
    (parallel/tp.py) and XLA inserts the tensor-parallel collectives."""
    axes = {ln.client_axis}
    if ln.sp:
        axes.add(ln.seq_axis)
    return frozenset(axes)


def donate_argnums(ln) -> tuple[int, ...]:
    """Donate the consumed round state (server_state, cohort variate
    block) so XLA reuses their HBM in place — matters for big models.
    CPU ignores donation with a warning, so skip."""
    devs = ln.mesh.devices.flat if ln.mesh is not None else jax.devices()
    first = next(iter(devs))
    return () if first.platform == "cpu" else (0, 8)


def cohort_step(ln, params, local_ids, global_ids, mask_cohort_ids,
                x, y, counts, key, round_idx,
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
    c = ln.config.fed
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
                slow, (frac * ln.num_steps).astype(jnp.int32), ln.num_steps
            )

        budgets = jax.vmap(budget_for)(global_ids)
    else:
        budgets = jnp.full((ln.cohort_size_local,), ln.num_steps, jnp.int32)

    # Round-level client-lr schedule factor, computed in-graph from
    # the round operand (no retrace, no host sync).
    lr_scale = strategies.lr_scale_for_round(c, round_idx)

    if ln.scaffold:
        c_i = c_blk                      # already one row per cohort slot
        sres = jax.vmap(
            ln.local_update,
            in_axes=(None, 0, 0, 0, 0, 0, 0, None, None),
        )(params, cx, cy, ccounts, keys, budgets, c_i, control, lr_scale)
        results = sres.result
    else:
        sres = None
        results = jax.vmap(
            ln.local_update, in_axes=(None, 0, 0, 0, 0, 0, None)
        )(params, cx, cy, ccounts, keys, budgets, lr_scale)
    deltas = results.delta
    completed = results.completed
    nova_a = None
    if ln.fednova:
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
    # Round telemetry: per-client update norms (the quantity operators
    # tune dp_clip against).  ONLY for non-private plain runs — under
    # DP the exact un-noised norms are an unaccounted release (the
    # adaptive path pays for even a 1-bit norm query), and under
    # secure-agg they are precisely what the masks exist to hide.
    track_norms = not (c.dp_clip > 0.0 or c.secure_agg)
    if track_norms:
        norms = jax.vmap(pytrees.tree_global_norm)(deltas)

    # SCAFFOLD averages uniformly over the sampled cohort (the variate
    # algebra assumes it); DP/secure-agg force uniform weights too.
    uniform_weights = (c.dp_clip > 0.0 or c.secure_agg or ln.scaffold
                       or ln.robust)
    bits = None
    if c.dp_clip > 0.0:
        dp_keys = jax.vmap(lambda i: prng.dp_key(key, i, round_idx))(global_ids)
        if ln.adaptive_clip:
            # Traced clip scalar + per-client quantile bit (pre-clip
            # norm <= clip), update noise at the inflated multiplier.
            deltas, bits = jax.vmap(
                lambda d, k: dp_lib.clip_and_noise_with_bit(
                    d, clip, ln.dp_z, ln.dp_cohort, k
                )
            )(deltas, dp_keys)
        else:
            deltas = jax.vmap(
                lambda d, k: dp_lib.clip_and_noise(
                    d, c.dp_clip, c.dp_noise_multiplier, ln.dp_cohort, k
                )
            )(deltas, dp_keys)

    nonghost = (results.num_examples > 0)
    # The ONE contributor mask (real, non-straggler) every aggregation
    # branch and metric below derives from.
    contrib = completed & nonghost
    if uniform_weights:
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
    elif ln.robust:
        # Coordinate-wise robust statistic over the FULL cohort
        # (fed/robust.py).  Order statistics are not psum-decomposable,
        # so on a mesh the stacked deltas are all-gathered over the
        # client axis first and the aggregate comes out replicated —
        # the round epilogue uses it directly (no psum, no division).
        from colearn_federated_learning_tpu.fed.robust import (
            robust_aggregate,
        )

        if ln.mesh is not None:
            ax = ln.client_axis
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
    if track_norms:
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
    if ln.scaffold:
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


def finish_round(ln, server_state, wsum, total_w, loss_sum, n_comp,
                 dc_sum=None, n_contrib=None, bit_sum=None, clip=None,
                 key=None, round_idx=None, norm_sum=None,
                 norm_max=None, nova_sum=None):
    """Shared round epilogue (vmap and shard_map paths): mean delta,
    server update, metrics.  Zero contributors (all stragglers) → no-op
    update; the explicit gate matters under secure_agg, where wsum is
    not exactly zero but the float32 mask-cancellation residual."""
    denom = jnp.where(total_w > 0, total_w, 1.0)
    if ln.robust:
        # wsum IS the robust aggregate (zero when nobody contributed);
        # total_w only normalizes the loss metric below.
        mean_delta = wsum
    else:
        mean_delta = pytrees.tree_scale(
            wsum, jnp.where(total_w > 0, 1.0 / denom, 0.0)
        )
    if ln.fednova and nova_sum is not None:
        # Rescale the mean of NORMALIZED deltas by the weighted-mean
        # step coefficient (tau_eff), completing d = tau_eff * mean.
        mean_delta = pytrees.tree_scale(mean_delta, nova_sum / denom)
    mean_delta_c = participation = None
    if ln.scaffold:
        safe_n = jnp.maximum(n_contrib, 1.0)
        mean_delta_c = pytrees.tree_scale(
            dc_sum, jnp.where(n_contrib > 0, 1.0 / safe_n, 0.0)
        )
        participation = n_contrib / float(ln.real_num_clients)
    new_state = strategies.server_update(server_state, mean_delta,
                                         ln.config.fed,
                                         mean_delta_c=mean_delta_c,
                                         participation=participation)
    metrics = {
        "train_loss": loss_sum / denom,
        "completed": n_comp,
        "total_weight": total_w,
    }
    track_norms = not (ln.config.fed.dp_clip > 0.0
                       or ln.config.fed.secure_agg)
    if norm_sum is not None and track_norms:
        safe_n = jnp.maximum(n_comp.astype(jnp.float32), 1.0)
        metrics["delta_norm_mean"] = norm_sum / safe_n
        metrics["delta_norm_max"] = norm_max
    if ln.adaptive_clip:
        # Noised quantile fraction -> geometric clip step.  In the
        # shard_map path this runs replicated AFTER the psums: every
        # device derives the identical noise from the shared key, so
        # the updated clip stays replicated.
        c = ln.config.fed
        bnoise = (
            ln.dp_bit_noise
            * jax.random.normal(prng.clip_bit_key(key, round_idx), ())
            if ln.dp_bit_noise > 0.0 else 0.0
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


def _build_vmap_round(ln):
    """Single-device path: clients are a vmap axis inside cohort_step."""

    def round_fn(server_state, key, round_idx, x, y, counts, ids,
                 sel_in, c_cohort, clip_in):
        if ln.scaffold:
            # Cohort-resident variates: the cohort was sampled on
            # host (so its variate rows could be gathered) and
            # arrives as an operand.
            sel = sel_in
        else:
            skey = prng.sampling_key(key, round_idx)
            if ln.cohort_size < ln.num_clients:
                sel = rank_cohort(skey, counts, ln.cohort_size)
            else:
                sel = jnp.arange(ln.num_clients)
        cohort_global = jnp.take(ids, sel)
        wsum, total_w, stats, extras = cohort_step(
            ln, server_state.params, sel, cohort_global,
            cohort_global, x, y, counts, key, round_idx,
            control=server_state.control, c_blk=c_cohort,
            clip=clip_in,
        )
        (loss_sum, n_comp, bit_sum, norm_sum, norm_max,
         nova_sum) = stats
        dc_sum, n_contrib, new_c = (
            extras if extras is not None else (None, None, None)
        )
        new_state, metrics = finish_round(
            ln, server_state, wsum, total_w, loss_sum, n_comp,
            dc_sum=dc_sum, n_contrib=n_contrib, bit_sum=bit_sum,
            clip=clip_in, key=key, round_idx=round_idx,
            norm_sum=norm_sum, norm_max=norm_max,
            nova_sum=nova_sum,
        )
        return new_state, metrics, new_c

    return jax.jit(round_fn, donate_argnums=donate_argnums(ln))


def _build_mesh_round(ln):
    """Multi-chip path: shard_map over the client axis (and, under SP,
    the sequence axis — every collective below names ONLY the client
    axis, so the ring collectives inside the model stay on ``seq``)."""
    mesh = ln.mesh
    ax = ln.client_axis
    local_clients = ln.num_clients // ln.clients_size

    def body(server_state, key, round_idx, x_blk, y_blk, counts_blk,
             ids_blk, sel_blk, c_blk, clip_in):
        if ln.scaffold:
            sel = sel_blk            # host-sampled (cohort-resident c)
        else:
            dev = jax.lax.axis_index(ax)
            skey = jax.random.fold_in(
                prng.sampling_key(key, round_idx), dev
            )
            if ln.cohort_per_device < local_clients:
                # This device's slice of the cohort among its REAL
                # clients (interleaved placement spreads reals evenly).
                sel = rank_cohort(skey, counts_blk,
                                   ln.cohort_per_device)
            else:
                sel = jnp.arange(local_clients)
        cohort_global = jnp.take(ids_blk, sel)
        # Secure-agg masks pair against the FULL mesh-wide cohort: a
        # cheap all_gather of the (cohort_per_device,) id vectors.
        mask_cohort = jax.lax.all_gather(cohort_global, ax).reshape(-1)
        wsum, total_w, stats, extras = cohort_step(
            ln, server_state.params, sel, cohort_global, mask_cohort,
            x_blk, y_blk, counts_blk, key, round_idx,
            control=server_state.control, c_blk=c_blk, clip=clip_in,
        )
        (loss_sum, n_comp, bit_sum, norm_sum, norm_max,
         nova_sum) = stats
        # FedAvg across the pod: one psum over ICI per leaf.  (Robust
        # aggregates are already global+replicated — no psum.)
        if not ln.robust:
            wsum = jax.tree.map(lambda l: jax.lax.psum(l, ax), wsum)
        total_w = jax.lax.psum(total_w, ax)
        loss_sum = jax.lax.psum(loss_sum, ax)
        n_comp = jax.lax.psum(n_comp, ax)
        bit_sum = jax.lax.psum(bit_sum, ax)
        norm_sum = jax.lax.psum(norm_sum, ax)
        norm_max = jax.lax.pmax(norm_max, ax)
        nova_sum = jax.lax.psum(nova_sum, ax)
        if extras is not None:
            dc_sum, n_contrib, new_c = extras
            dc_sum = jax.tree.map(lambda l: jax.lax.psum(l, ax), dc_sum)
            n_contrib = jax.lax.psum(n_contrib, ax)
        else:
            dc_sum, n_contrib, new_c = None, None, None
        new_state, metrics = finish_round(
            ln, server_state, wsum, total_w, loss_sum, n_comp,
            dc_sum=dc_sum, n_contrib=n_contrib, bit_sum=bit_sum,
            clip=clip_in, key=key, round_idx=round_idx,
            norm_sum=norm_sum, norm_max=norm_max,
            nova_sum=nova_sum,
        )
        return new_state, metrics, new_c

    x_spec = P(ax, None, ln.seq_axis) if ln.sp else P(ax)
    c_spec = P(ax) if ln.scaffold else P()
    sel_spec = P(ax) if ln.scaffold else P()
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P(), x_spec, P(ax), P(ax), P(ax), sel_spec,
                  c_spec, P()),
        out_specs=(P(), P(), c_spec),
        axis_names=manual_axes(ln),
        check_vma=False,
    )
    # The state goes back out placed as it came in.  Left to itself the
    # partitioner may lay a replicated leaf over the auto ``model`` axis
    # (MoE routers), and jit would compile the whole program again for
    # the second round's new argument placement.
    state_shardings = jax.tree.map(lambda l: l.sharding, ln.server_state)
    return jax.jit(sharded, donate_argnums=donate_argnums(ln),
                   out_shardings=(state_shardings, None, None))


def build_round_fn(ln):
    """The one entry the engine calls: dispatch on mesh presence; both
    builders honor the shared signature documented in the module
    docstring (``ln.cohort_size_local`` is set by the engine before the
    call — cohort_size on the vmap path, cohort_per_device on the mesh
    path)."""
    return _build_vmap_round(ln) if ln.mesh is None else _build_mesh_round(ln)


# ---------------------------------------------------------------------
# per-client programs (eval / personalization / similarity)
# ---------------------------------------------------------------------
def build_client_eval_fn(ln):
    """Per-client (loss, acc) of the CURRENT global params on each
    client's own shard — vmapped, sharded over the client axis on a
    mesh.  Chunked scan bounds activation memory."""
    batch = eval_rows(ln.config.fed.batch_size, ln.shards.x[0])
    cap = ln.shards.capacity
    n_chunks = int(np.ceil(cap / batch))
    padded = n_chunks * batch
    # Under SP the shard data arrives sequence-sharded, so the eval
    # must run the ring-attention (SP-aware) module, not the dense twin.
    apply_fn = (ln.model if ln.sp else ln.eval_model).apply

    def one_client(params, cx, cy, count):
        # Pad the shard to whole chunks; only rows < count score.
        pad = padded - cap
        cxp = jnp.concatenate(
            [cx, jnp.zeros((pad,) + cx.shape[1:], cx.dtype)]
        ) if pad else cx
        cyp = jnp.concatenate(
            [cy, jnp.zeros((pad,) + cy.shape[1:], cy.dtype)]
        ) if pad else cy
        xb = cxp.reshape((n_chunks, batch) + cx.shape[1:])
        yb = cyp.reshape((n_chunks, batch) + cy.shape[1:])
        base = jnp.arange(n_chunks) * batch

        def step(carry, inp):
            x_, y_, b = inp
            logits = apply_fn({"params": params}, x_, train=False)
            ce = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(ce, y_[..., None], axis=-1)[..., 0]
            correct = (jnp.argmax(logits, axis=-1) == y_).astype(jnp.float32)
            m = per_label(
                ((b + jnp.arange(batch)) < count).astype(jnp.float32), nll)
            l, a, n = carry
            return (l + jnp.sum(nll * m), a + jnp.sum(correct * m),
                    n + jnp.sum(m)), None

        (l, a, n), _ = jax.lax.scan(step, (0.0, 0.0, 0.0), (xb, yb, base))
        n = jnp.maximum(n, 1.0)
        return l / n, a / n

    vmapped = jax.vmap(one_client, in_axes=(None, 0, 0, 0))
    if ln.mesh is None:
        return jax.jit(vmapped)

    ax = ln.client_axis
    x_spec = P(ax, None, ln.seq_axis) if ln.sp else P(ax)
    return jax.jit(jax.shard_map(
        vmapped, mesh=ln.mesh,
        in_specs=(P(), x_spec, P(ax), P(ax)),
        out_specs=(P(ax), P(ax)),
        axis_names=manual_axes(ln),
        check_vma=False,
    ))


def build_personalized_eval_fn(ln, steps: int, lr: float):
    """Fine-tune-then-eval probe: ``steps`` local SGD steps on the first
    half of each client's shard, score global vs personalized params on
    the second half (fed/engine.evaluate_personalized)."""
    import dataclasses

    from colearn_federated_learning_tpu.fed import setup as setup_lib

    c = ln.config
    apply_fn = (ln.model if ln.sp else ln.eval_model).apply
    # The fine-tune is the CONFIG's local trainer (same optimizer,
    # momentum, MoE aux loss, prox term) with the step budget and lr
    # overridden — setup_lib keeps the wiring identical to training.
    ft_config = c.replace(fed=dataclasses.replace(
        c.fed,
        strategy=c.fed.strategy if c.fed.strategy == "fedprox" else "fedavg",
        local_steps=steps, lr=lr, straggler_prob=0.0,
    ))
    update, _ = setup_lib.local_trainer_for_config(
        ft_config, apply_fn, ln.shards.capacity,
        grad_sync_axes=(ln.seq_axis,) if ln.sp else (),
        param_axes=(ln.tp_axis,) if ln.tp_size > 1 else (),
    )
    budget = jnp.asarray(steps, jnp.int32)
    batch = eval_rows(c.fed.batch_size, ln.shards.x[0])
    cap = ln.shards.capacity
    n_chunks = int(np.ceil(cap / batch))
    padded = n_chunks * batch

    def score(params, cx, cy, lo, hi):
        """Mean accuracy over shard rows [lo, hi), scanned in
        batch-sized chunks (bounded activation memory, same scheme as
        build_client_eval_fn)."""
        pad = padded - cap
        cxp = jnp.concatenate(
            [cx, jnp.zeros((pad,) + cx.shape[1:], cx.dtype)]
        ) if pad else cx
        cyp = jnp.concatenate(
            [cy, jnp.zeros((pad,) + cy.shape[1:], cy.dtype)]
        ) if pad else cy
        xb = cxp.reshape((n_chunks, batch) + cx.shape[1:])
        yb = cyp.reshape((n_chunks, batch) + cy.shape[1:])
        base = jnp.arange(n_chunks) * batch

        def chunk(carry, inp):
            x_, y_, b = inp
            logits = apply_fn({"params": params}, x_, train=False)
            correct = (jnp.argmax(logits, axis=-1) == y_).astype(jnp.float32)
            rows = b + jnp.arange(batch)
            m = per_label(
                ((rows >= lo) & (rows < hi)).astype(jnp.float32), correct)
            a, n = carry
            return (a + jnp.sum(correct * m), n + jnp.sum(m)), None

        (a, n), _ = jax.lax.scan(chunk, (0.0, 0.0), (xb, yb, base))
        return a / jnp.maximum(n, 1.0)

    def one_client(params, cx, cy, count, gid):
        n_ft = count // 2                       # fine-tune half
        n_eval = jnp.where(count >= 2, count - n_ft, 0)
        # Purpose-distinct key: round index past any training round.
        key = prng.client_round_key(
            ln.base_key, gid, jnp.asarray(1 << 24, jnp.int32)
        )
        res = update(params, cx, cy, jnp.maximum(n_ft, 1), key, budget)
        pers = pytrees.tree_add(params, res.delta)
        g_acc = score(params, cx, cy, n_ft, count)
        p_acc = score(pers, cx, cy, n_ft, count)
        return g_acc, p_acc, n_eval

    vmapped = jax.vmap(one_client, in_axes=(None, 0, 0, 0, 0))
    if ln.mesh is None:
        return jax.jit(vmapped)
    ax = ln.client_axis
    x_spec = P(ax, None, ln.seq_axis) if ln.sp else P(ax)
    return jax.jit(jax.shard_map(
        vmapped, mesh=ln.mesh,
        in_specs=(P(), x_spec, P(ax), P(ax), P(ax)),
        out_specs=(P(ax), P(ax), P(ax)),
        axis_names=manual_axes(ln),
        check_vma=False,
    ))


def build_similarity_fn(ln, steps: int):
    """(N, N) cosine-similarity program over every client's local update
    (clustered FL signal; fed/engine.client_update_similarity documents
    the mesh strategy — all_gather the normalized deltas, per-device gram
    strips on the MXU)."""
    budget = jnp.asarray(min(steps, ln.num_steps), jnp.int32)

    def flat_norm_deltas(params, x, y, counts, ids, key, n_rows):
        keys = jax.vmap(
            lambda i: prng.client_round_key(key, i, 1 << 23)
        )(ids)
        budgets = jnp.full((n_rows,), budget, jnp.int32)
        res = jax.vmap(ln.local_update,
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

    if ln.mesh is None:
        def sim(params, x, y, counts, ids, key):
            Xn = flat_norm_deltas(params, x, y, counts, ids, key,
                                  ln.num_clients)
            return Xn @ Xn.T

        return jax.jit(sim)

    ax = ln.client_axis
    local_clients = ln.num_clients // ln.clients_size

    def sim_body(params, x_blk, y_blk, counts_blk, ids_blk, key):
        Xn = flat_norm_deltas(params, x_blk, y_blk, counts_blk,
                              ids_blk, key, local_clients)
        x_all = jax.lax.all_gather(Xn, ax)
        x_all = x_all.reshape(-1, Xn.shape[1])     # (N, P)
        return Xn @ x_all.T                        # (N/D, N)

    x_spec = (P(ax, None, ln.seq_axis) if ln.sp
              else P(ax))
    return jax.jit(jax.shard_map(
        sim_body,
        mesh=ln.mesh,
        in_specs=(P(), x_spec, P(ax), P(ax), P(ax), P()),
        out_specs=P(ax, None),
        axis_names=manual_axes(ln),
        check_vma=False,
    ))
