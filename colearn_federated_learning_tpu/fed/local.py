"""Jit-compiled local training: one client's whole round as a single lax.scan.

The reference's hot loop is a Python ``for epoch: for batch:`` PyTorch loop
inside each PySyft worker process (SURVEY.md §3c).  Here the entire local
round — E epochs of minibatch SGD, optionally with a FedProx proximal term —
is one ``lax.scan`` over steps, compiled once and then ``vmap``-ed over the
client axis (single chip) or ``shard_map``-ed over a mesh (multi chip), per
BASELINE.json ``north_star`` ("each TPU core simulates one client running
jit-compiled local SGD").

Straggler handling (SURVEY.md §5 "failure detection"): the scan always runs
the full static step count, but each client carries a ``step_budget``; steps
past the budget are masked to no-ops with ``jnp.where``, so a straggler's
partial progress exists but its FedAvg weight is zeroed by the engine when
the budget falls below the completion threshold.  Shapes stay static — no
recompilation per round (SURVEY.md §7 hard part #2).

Working set of rows.  A model may declare leaves that it reads only by
gathering the rows its integer input names (``gathered_tables`` of
models/bert.py: the token-embedding table).  A client's round can touch at
most ``K = num_steps * batch * tokens per example`` of such a table's ``V``
rows, so where ``K < V`` the scan runs on a ``[K, D]`` leaf in the table's
place: the round's batches are drawn before the scan (the same keys give the
same indices), their distinct ids are sorted into ``K`` slots, the table's
rows at those ids take the table's place in the parameter tree, and the
model's gather reads positions in the slots.  After the scan the rows'
change is scattered into a dense ``[V, D]`` delta of zeros, so
``LocalResult`` is shaped as ever.  The result is the same because an
untouched row has zero gradient in every step and fresh optimizer state, and
the optimizer leaves such a row where it is (exactly 0, as the dense path
computes at full cost), while a row touched in any step of the round is in
the working set for all of them, moments included; only the order of a row's
scatter-adds may differ.  It engages when all of this can be seen: a declared
leaf, an integer input, ``K < V``, an optimizer that is probed to leave such
rows alone (``sgd``, ``adam``; not ``adamw``, whose decay moves every row),
no SCAFFOLD correction (dense), no ``grad_sync_axes`` (each sequence shard
sees other ids) and no ``param_axes`` (a table sharded by vocabulary).
Otherwise the dense path runs, and its program is what it was.  Ids are
taken to lie in ``[0, V)``, as ``nn.Embed`` requires.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.fed import losses
from colearn_federated_learning_tpu.parallel.partition import path_str
from colearn_federated_learning_tpu.utils import pytrees


class LocalResult(NamedTuple):
    delta: Any               # params pytree: local_params - global_params
    num_examples: jnp.ndarray  # () int32 — true shard size (FedAvg weight)
    completed: jnp.ndarray     # () bool — ran >= min required steps
    mean_loss: jnp.ndarray     # () float32 over executed steps
    steps_run: jnp.ndarray     # () float32 — executed step count (FedNova
                               # normalizes by it; varies under stragglers)


class ScaffoldResult(NamedTuple):
    result: LocalResult
    c_new: Any               # this client's updated control variate
    delta_c: Any             # c_new - c_old (server control update)


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def make_optimizer(lr: float, momentum: float,
                   name: str = "sgd") -> optax.GradientTransformation:
    """Client-side optimizer.

    ``sgd``: plain SGD(+momentum) matching torch semantics: buf = m*buf + g;
    p -= lr*buf (optax ``trace`` with nesterov=False, SURVEY.md §7 hard
    part #4 — optimizer parity with the reference's PyTorch SGD).
    ``adam`` / ``adamw``: adaptive local optimizers (common for the text
    configs; the reference's workers run whatever torch.optim they choose).
    """
    if name == "sgd":
        if momentum > 0:
            return optax.sgd(lr, momentum=momentum, nesterov=False)
        return optax.sgd(lr)
    if name == "adam":
        return optax.adam(lr)
    if name == "adamw":
        return optax.adamw(lr)
    raise ValueError(f"unknown local optimizer {name!r} (sgd|adam|adamw)")


def _sown_aux_mean(intermediates) -> jnp.ndarray | None:
    """Mean of all ``moe_aux`` values sown during apply (models/moe.py's
    Switch load-balance loss, one per MoE layer); None when nothing sown."""
    vals = [
        leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates)
        if any(getattr(p, "key", None) == "moe_aux" for p in path)
    ]
    if not vals:
        return None
    return sum(vals) / len(vals)


def _batch_indices(key, t, batch_size: int, count):
    """Step ``t``'s batch: ``batch_size`` uniform draws from [0, count)."""
    return jax.random.randint(
        jax.random.fold_in(key, t), (batch_size,), 0, count)


def _rows_move_alone(optimizer: optax.GradientTransformation) -> bool:
    """Probe of what the working set of rows rests on: a row with zero
    gradient and fresh state gets the update 0 and keeps its state, and
    the other rows get the update they would get without it.  True of
    sgd(+momentum) and adam; not of adamw (its decay moves every row) nor
    of anything that couples a leaf's rows (a trust ratio)."""
    p = jnp.array([[3.0, -2.0], [0.5, 4.0]])
    g = jnp.array([[0.0, 0.0], [0.25, -1.0]])

    def probe():
        state = optimizer.init(p)
        updates, new_state = optimizer.update(g, state, p)
        alone, _ = optimizer.update(g[1:], optimizer.init(p[1:]), p[1:])
        ok = jnp.all(updates[0] == 0) & jnp.all(updates[1:] == alone)
        for old, new in zip(jax.tree.leaves(state),
                            jax.tree.leaves(new_state)):
            if jnp.shape(old) == p.shape:
                ok &= jnp.all(old[0] == new[0])
        return ok

    return bool(jax.jit(probe)())


class _WorkingSet(NamedTuple):
    """The rows one client's round can touch (module docstring)."""
    ids: jnp.ndarray    # (K,) the round's distinct ids, sorted; free slots
                        # hold an id past every table
    rows: jnp.ndarray   # (num_steps, batch, ...) each token's slot in ids

    @classmethod
    def of(cls, ids_all) -> "_WorkingSet":
        ids, rows = jnp.unique(
            ids_all, size=ids_all.size, return_inverse=True,
            fill_value=jnp.iinfo(ids_all.dtype).max)
        return cls(ids, rows.reshape(ids_all.shape))

    def gather(self, table):
        # Free slots read the last row; nothing gathers them, so they have
        # no gradient, do not move, and are dropped by ``scatter``.
        return jnp.take(table, self.ids, axis=0, mode="clip")

    def scatter(self, rows_delta, table):
        return jnp.zeros_like(table).at[self.ids].set(rows_delta, mode="drop")


def _leaf(tree, path: str):
    for name in path.split("/"):
        tree = tree[name]
    return tree


def _map_leaves(paths, fn, tree, *rest):
    """``tree`` with ``fn(leaf, *leaves of rest)`` at the '/'-joined key
    ``paths``."""
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf, *others: (fn(leaf, *others) if path_str(p) in paths
                                  else leaf),
        tree, *rest)


def make_local_update(
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    num_steps: int,
    batch_size: int,
    prox_mu: float = 0.0,
    min_steps_fraction: float = 0.25,
    grad_sync_axes: tuple[str, ...] = (),
    scaffold: bool = False,
    lr: float = 0.0,
    aux_loss_weight: float = 0.0,
    param_axes: tuple[str, ...] = (),
) -> Callable:
    """Build ``local_update(global_params, x, y, count, key, step_budget)``.

    With ``scaffold=True`` the signature gains trailing ``(c_i, c)``
    control-variate pytrees and the return becomes a ``ScaffoldResult``
    (SCAFFOLD, Karimireddy et al. 2019: per-step grads are corrected by
    ``- c_i + c``, and the client's variate refreshes via option II,
    ``c_i' = c_i - c + (w_global - w_local)/(K·lr)`` over the K executed
    steps).  ``lr`` must then be the client learning rate.

    - ``x``: (M, ...) padded shard, ``y``: (M,), ``count``: () true size.
    - ``num_steps`` is the static per-round step budget (epochs * ceil(M/B)).
    - Sampling: each step draws ``batch_size`` uniform indices in
      [0, count) — i.i.d. sampling-with-replacement, the standard choice for
      static-shape federated simulation.
    - ``grad_sync_axes``: mesh axes the model's activations are sharded
      over (sequence parallelism).  Per-step grads are pmean'd over them —
      paired with the model's ``psum_for_grad_pmean`` pooling collective
      (parallel/collectives.py) this reconstructs exact full-sequence grads
      on every shard, so params stay replicated through local training.
    - ``param_axes``: mesh axes the parameters are sharded over (tensor
      parallelism: the embedding table by vocabulary); with any, the
      working set of rows (module docstring) stays off.
    """
    min_steps = max(1, int(num_steps * min_steps_fraction))
    # Build-time only — the returned closure is jit-traced, where Python
    # side effects would silently run once and vanish.
    from colearn_federated_learning_tpu.telemetry import get_registry

    reg = get_registry()
    reg.counter("local.trainers_built").inc()
    reg.gauge("local.steps_per_round").set(num_steps)

    # Leaves the model reads only by gathering rows of its integer input
    # ('/'-joined path -> the module field that sizes the leaf), kept only
    # where training on a working set of their rows gives the dense result.
    module = getattr(apply_fn, "__self__", None)
    tables = dict(getattr(module, "gathered_tables", {}))
    if (scaffold or grad_sync_axes or param_axes
            or not (tables and _rows_move_alone(optimizer))):
        tables = {}
    owners = {tuple(path.split("/")[:-1]) for path in tables}   # modules
    # K and V are shapes, first seen when the trainer is traced: its tables
    # are counted there, once a trainer and not once a trace.
    compacted = reg.counter("local.compact_tables")
    counted: list = []

    def forward(params, xb, rows, **kw):
        """The model's training pass; given ``rows``, on a working set: the
        declared tables have the rows their leaves in ``params`` have, and
        their gathers read ``rows`` where the model hands them ``xb``."""
        if rows is None:
            return apply_fn({"params": params}, xb, train=True, **kw)
        sized = module.clone(**{field: _leaf(params, path).shape[0]
                                for path, field in tables.items()})

        def read_rows(next_fun, args, kwargs, context):
            if (context.method_name == "__call__"
                    and context.module.path in owners):
                return next_fun(rows)
            return next_fun(*args, **kwargs)

        with nn.intercept_methods(read_rows):
            return sized.apply({"params": params}, xb, train=True, **kw)

    def loss_fn(params, global_params, xb, yb, rows=None):
        if aux_loss_weight > 0.0:
            # MoE models sow their load-balance loss into "intermediates";
            # running every model this way would be harmless (flax returns
            # an empty dict) but the mutable round-trip is only paid when
            # the config asks for it.
            logits, updates = forward(
                params, xb, rows, mutable=["intermediates"])
            aux = _sown_aux_mean(updates.get("intermediates", {}))
            extra = aux_loss_weight * aux if aux is not None else 0.0
        else:
            logits = forward(params, xb, rows)
            extra = 0.0
        loss = losses.softmax_cross_entropy(logits, yb) + extra
        if prox_mu > 0.0:
            # FedProx: + μ/2 ‖w − w_global‖² (BASELINE config #3, μ=0.01).
            # Under SP its grads flow through the (replicated) params on
            # every shard; the pmean convention keeps that exact.
            loss = loss + 0.5 * prox_mu * pytrees.tree_sq_norm(
                pytrees.tree_sub(params, global_params)
            )
        return loss

    grad_fn = jax.value_and_grad(loss_fn)

    if scaffold and lr <= 0.0:
        raise ValueError("scaffold=True requires the client lr")

    def working_set(global_params, x, count, key):
        """The round's ``_WorkingSet``, or None where the dense path runs."""
        if not tables or not jnp.issubdtype(x.dtype, jnp.integer):
            return None
        k = num_steps * batch_size * math.prod(x.shape[1:])
        v = min(_leaf(global_params, path).shape[0] for path in tables)
        if k >= v:
            return None
        if not counted:
            counted.append(True)
            compacted.inc(len(tables))
            reg.gauge("local.compact_rows").set(k)
            reg.gauge("local.compact_rows_of").set(v)
        # The steps' own draws, made here as well: the scan is left as it
        # is, so the dense path's program is what it was.
        idx_all = jax.vmap(
            lambda t: _batch_indices(key, t, batch_size,
                                     jnp.maximum(count, 1))
        )(jnp.arange(num_steps))
        return _WorkingSet.of(jnp.take(x, idx_all, axis=0))

    def run_steps(global_params, x, y, count, key, step_budget, correction,
                  lr_scale):
        dense_params = global_params
        ws = working_set(global_params, x, count, key)
        if ws is not None:
            global_params = _map_leaves(tables, ws.gather, dense_params)
        with telemetry.device_scope("local.optimizer"):
            opt_state = optimizer.init(global_params)
        safe_count = jnp.maximum(count, 1)

        def step(carry, inp):
            params, opt_state = carry
            t, rows = inp
            idx = _batch_indices(key, t, batch_size, safe_count)
            xb = jnp.take(x, idx, axis=0)
            yb = jnp.take(y, idx, axis=0)
            loss, grads = grad_fn(params, global_params, xb, yb, rows)
            for ax in grad_sync_axes:
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, ax), grads)
            if correction is not None:
                grads = pytrees.tree_add(grads, correction)
            # The client's state (its initial value, its update, what it
            # moved by), apart from the model's passes on the device trace.
            with telemetry.device_scope("local.optimizer"):
                updates, new_opt_state = optimizer.update(
                    grads, opt_state, params)
                if lr_scale is not None:
                    # Round-level lr schedule (strategies.
                    # lr_scale_for_round): scaling the UPDATE equals
                    # running at lr·scale for SGD (+momentum, linear in lr
                    # from a zero buffer) and for Adam (update ∝ lr; grad
                    # scaling would be a no-op there).
                    updates = pytrees.tree_scale(updates, lr_scale)
                new_params = optax.apply_updates(params, updates)
                active = t < step_budget
                params = _tree_where(active, new_params, params)
                opt_state = _tree_where(active, new_opt_state, opt_state)
            return (params, opt_state), loss * active

        (params, _), step_losses = jax.lax.scan(
            step, (global_params, opt_state),
            (jnp.arange(num_steps), None if ws is None else ws.rows),
        )
        executed = jnp.minimum(step_budget, num_steps).astype(jnp.float32)
        mean_loss = jnp.sum(step_losses) / jnp.maximum(executed, 1.0)
        with telemetry.device_scope("local.optimizer"):
            delta = pytrees.tree_sub(params, global_params)
        if ws is not None:
            delta = _map_leaves(tables, ws.scatter, delta, dense_params)
        result = LocalResult(
            delta=delta,
            num_examples=count.astype(jnp.int32),
            completed=step_budget >= min_steps,
            mean_loss=mean_loss,
            steps_run=executed,
        )
        return result, executed

    if not scaffold:
        def local_update(global_params, x, y, count, key, step_budget,
                         lr_scale=None):
            result, _ = run_steps(global_params, x, y, count, key,
                                  step_budget, None, lr_scale)
            return result

        return local_update

    def scaffold_update(global_params, x, y, count, key, step_budget, c_i, c,
                        lr_scale=None):
        correction = pytrees.tree_sub(c, c_i)     # grads - c_i + c
        result, executed = run_steps(global_params, x, y, count, key,
                                     step_budget, correction, lr_scale)
        # Option II refresh: c_i' = c_i - c + (w_g - w_local)/(K·lr_eff),
        # where lr_eff folds in the round-level schedule factor.  Past a
        # zero-floor cosine horizon lr_eff hits 0 while delta is exactly
        # 0 — clamp so the refresh stays 0/eps = finite instead of 0·inf
        # = NaN poisoning the variates.
        lr_eff = lr if lr_scale is None else lr * lr_scale
        scale = 1.0 / (jnp.maximum(executed, 1.0)
                       * jnp.maximum(lr_eff, 1e-12))
        c_new = pytrees.tree_add(
            pytrees.tree_sub(c_i, c),
            pytrees.tree_scale(result.delta, -scale),
        )
        return ScaffoldResult(
            result=result,
            c_new=c_new,
            delta_c=pytrees.tree_sub(c_new, c_i),
        )

    return scaffold_update


def make_lora_local_update(
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    num_steps: int,
    batch_size: int,
    rank: int,
    alpha: float,
    prox_mu: float = 0.0,
    min_steps_fraction: float = 0.25,
    aux_loss_weight: float = 0.0,
) -> Callable:
    """Build ``lora_update(base_params, factors, x, y, count, key,
    step_budget, lr_scale=None)`` — the factor-only twin of
    :func:`make_local_update`.

    The base params are a FROZEN constant of the loss: autodiff runs
    w.r.t. the factor tree only, the forward pass applies the adapters
    through :func:`fed.lora.apply_adapters`, and the returned
    ``LocalResult.delta`` is a FACTOR delta (trained - received factors)
    — the O(r·d) tree the uplink ships.  Structure mirrors the dense
    trainer exactly (same scan, same per-step fold_in sampling, same
    ``step_budget`` masking, same lr_scale semantics), so shapes stay
    static and the jitted program holds ONE compile signature across
    rounds (pinned via telemetry CompileTracker in tests).

    ``prox_mu`` applies FedProx's proximal pull on the FACTORS
    (``mu/2 * ||f - f_global||^2``) — the natural restriction when the
    factors are the only trainable coordinates."""
    from colearn_federated_learning_tpu.fed import lora

    min_steps = max(1, int(num_steps * min_steps_fraction))
    from colearn_federated_learning_tpu.telemetry import get_registry

    reg = get_registry()
    reg.counter("local.trainers_built").inc()
    reg.gauge("local.steps_per_round").set(num_steps)

    def loss_fn(factors, base_params, global_factors, xb, yb):
        params = lora.apply_adapters(base_params, factors, alpha, rank)
        if aux_loss_weight > 0.0:
            logits, updates = apply_fn(
                {"params": params}, xb, train=True, mutable=["intermediates"]
            )
            aux = _sown_aux_mean(updates.get("intermediates", {}))
            extra = aux_loss_weight * aux if aux is not None else 0.0
        else:
            logits = apply_fn({"params": params}, xb, train=True)
            extra = 0.0
        loss = losses.softmax_cross_entropy(logits, yb) + extra
        if prox_mu > 0.0:
            loss = loss + 0.5 * prox_mu * pytrees.tree_sq_norm(
                pytrees.tree_sub(factors, global_factors)
            )
        return loss

    grad_fn = jax.value_and_grad(loss_fn)

    def lora_update(base_params, factors, x, y, count, key, step_budget,
                    lr_scale=None):
        opt_state = optimizer.init(factors)
        safe_count = jnp.maximum(count, 1)

        def step(carry, t):
            f, opt_state = carry
            k = jax.random.fold_in(key, t)
            idx = jax.random.randint(k, (batch_size,), 0, safe_count)
            xb = jnp.take(x, idx, axis=0)
            yb = jnp.take(y, idx, axis=0)
            loss, grads = grad_fn(f, base_params, factors_in, xb, yb)
            updates, new_opt_state = optimizer.update(grads, opt_state, f)
            if lr_scale is not None:
                updates = pytrees.tree_scale(updates, lr_scale)
            new_f = optax.apply_updates(f, updates)
            active = t < step_budget
            f = _tree_where(active, new_f, f)
            opt_state = _tree_where(active, new_opt_state, opt_state)
            return (f, opt_state), loss * active

        factors_in = factors
        (f, _), step_losses = jax.lax.scan(
            step, (factors, opt_state), jnp.arange(num_steps)
        )
        executed = jnp.minimum(step_budget, num_steps).astype(jnp.float32)
        mean_loss = jnp.sum(step_losses) / jnp.maximum(executed, 1.0)
        return LocalResult(
            delta=pytrees.tree_sub(f, factors_in),
            num_examples=count.astype(jnp.int32),
            completed=step_budget >= min_steps,
            mean_loss=mean_loss,
            steps_run=executed,
        )

    return lora_update
