"""Local trainer: loss decreases, straggler budgets mask updates, FedProx pulls."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.fed import local as local_lib
from colearn_federated_learning_tpu.fed import losses
from colearn_federated_learning_tpu.models.bert import BertClassifier
from colearn_federated_learning_tpu.models.cnn import CNN
from colearn_federated_learning_tpu.models.mlp import MLP
from colearn_federated_learning_tpu.utils import prng, pytrees


def _toy_problem(seed=0, n=128, d=8, k=3):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(d, k))
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rng.normal(size=(n, k)), axis=1).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _setup(num_steps=20, prox_mu=0.0, lr=0.1):
    model = MLP(num_classes=3, hidden_dim=16, depth=1)
    x, y = _toy_problem()
    params = model.init(jax.random.PRNGKey(0), x[:4])["params"]
    opt = local_lib.make_optimizer(lr, 0.9)
    update = local_lib.make_local_update(
        model.apply, opt, num_steps=num_steps, batch_size=16, prox_mu=prox_mu
    )
    return model, params, x, y, update


def test_local_update_learns():
    model, params, x, y, update = _setup()
    key = prng.client_round_key(prng.experiment_key(0), 0, 0)
    res = update(params, x, y, jnp.asarray(len(x)), key, jnp.asarray(20))
    assert bool(res.completed)
    assert int(res.num_examples) == 128
    # Moved away from init, and the last steps beat the first steps.
    assert float(pytrees.tree_global_norm(res.delta)) > 0.0

    logits0 = model.apply({"params": params}, x)
    p1 = jax.tree.map(lambda a, b: a + b, params, res.delta)
    logits1 = model.apply({"params": p1}, x)
    acc0 = float((jnp.argmax(logits0, -1) == y).mean())
    acc1 = float((jnp.argmax(logits1, -1) == y).mean())
    assert acc1 > acc0


def test_zero_budget_is_noop_and_incomplete():
    _, params, x, y, update = _setup()
    key = prng.experiment_key(1)
    res = update(params, x, y, jnp.asarray(len(x)), key, jnp.asarray(0))
    assert float(pytrees.tree_global_norm(res.delta)) == 0.0
    assert not bool(res.completed)


def test_partial_budget_partial_progress():
    _, params, x, y, update = _setup(num_steps=20)
    key = prng.experiment_key(2)
    res_full = update(params, x, y, jnp.asarray(len(x)), key, jnp.asarray(20))
    res_half = update(params, x, y, jnp.asarray(len(x)), key, jnp.asarray(10))
    n_full = float(pytrees.tree_global_norm(res_full.delta))
    n_half = float(pytrees.tree_global_norm(res_half.delta))
    assert 0.0 < n_half < n_full
    assert bool(res_half.completed)  # 10 >= 25% of 20


def test_fedprox_term_shrinks_delta():
    _, params, x, y, update0 = _setup(prox_mu=0.0)
    _, _, _, _, update_prox = _setup(prox_mu=10.0)
    key = prng.experiment_key(3)
    d0 = update0(params, x, y, jnp.asarray(len(x)), key, jnp.asarray(20)).delta
    dp = update_prox(params, x, y, jnp.asarray(len(x)), key, jnp.asarray(20)).delta
    assert float(pytrees.tree_global_norm(dp)) < float(pytrees.tree_global_norm(d0))


def test_vmap_over_clients_matches_single():
    _, params, x, y, update = _setup()
    key0 = prng.client_round_key(prng.experiment_key(0), 0, 0)
    key1 = prng.client_round_key(prng.experiment_key(0), 1, 0)
    xs = jnp.stack([x, x * 0.5])
    ys = jnp.stack([y, y])
    counts = jnp.asarray([128, 128])
    keys = jnp.stack([key0, key1])
    budgets = jnp.asarray([20, 20])
    batched = jax.vmap(update, in_axes=(None, 0, 0, 0, 0, 0))(
        params, xs, ys, counts, keys, budgets
    )
    single = update(params, x, y, jnp.asarray(128), key0, jnp.asarray(20))
    for a, b in zip(jax.tree.leaves(batched.delta), jax.tree.leaves(single.delta)):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b), rtol=2e-4, atol=1e-5)


# --- working set of rows (fed/local.py module docstring) -----------------

VOCAB, SEQ, STEPS, BATCH = 400, 8, 3, 4          # K = 96 of 400 rows
TABLE = ("Embed_0", "embedding")


class DenseBert(BertClassifier):
    """The same model without the declaration: the dense path."""
    gathered_tables = {}


def _bert(cls=BertClassifier, vocab=VOCAB):
    return cls(num_classes=4, vocab_size=vocab, embed_dim=16, depth=1,
               num_heads=2, max_len=SEQ)


def _text_problem(seed=0, n=40, vocab=VOCAB, clients=None):
    rng = np.random.default_rng(seed)
    shape = (n, SEQ) if clients is None else (clients, n, SEQ)
    x = rng.integers(1, vocab, size=shape).astype(np.int32)
    x[..., -2:] = 0                                  # padding, as real text
    y = rng.integers(0, 4, size=shape[:-1]).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _compact_tables() -> float:
    return telemetry.get_registry().counter("local.compact_tables").value


def _assert_same_result(got, want, x_seen):
    """``got`` (working set) against ``want`` (dense): touched rows and
    every other leaf to rtol 1e-6 (atol: an ulp of a parameter, which under
    Adam is 1e-5 of its change), untouched rows bitwise 0 in both."""
    for a, b in zip(jax.tree.leaves(got.delta), jax.tree.leaves(want.delta)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1.5e-7)
    untouched = np.setdiff1d(np.arange(VOCAB), np.unique(x_seen))
    assert len(untouched) >= VOCAB - STEPS * BATCH * SEQ
    for res in (got, want):
        table = np.asarray(res.delta[TABLE[0]][TABLE[1]])
        assert not table[untouched].view(np.uint32).any()    # +0.0, bitwise
        assert np.abs(table).sum() > 0
    np.testing.assert_allclose(got.mean_loss, want.mean_loss, rtol=1e-6)
    assert float(got.steps_run) == float(want.steps_run)
    assert bool(got.completed) == bool(want.completed)


@pytest.mark.parametrize("budget", [2, STEPS], ids=["budget_below", "budget_full"])
@pytest.mark.parametrize("opt_name,momentum", [("adam", 0.0), ("sgd", 0.9)],
                         ids=["adam", "sgd_momentum"])
def test_working_set_matches_dense(opt_name, momentum, budget):
    x, y = _text_problem()
    params = _bert().init(jax.random.PRNGKey(0), x[:2])["params"]
    opt = local_lib.make_optimizer(0.01, momentum, opt_name)
    before = _compact_tables()
    kw = dict(num_steps=STEPS, batch_size=BATCH, prox_mu=0.1)       # FedProx on
    compact = jax.jit(local_lib.make_local_update(_bert().apply, opt, **kw))
    dense = jax.jit(local_lib.make_local_update(_bert(DenseBert).apply, opt, **kw))
    key = prng.client_round_key(prng.experiment_key(0), 3, 1)
    args = (params, x, y, jnp.asarray(len(x)), key, jnp.asarray(budget),
            jnp.asarray(0.5, jnp.float32))                           # lr_scale
    got, want = compact(*args), dense(*args)
    assert _compact_tables() - before == 1          # one of the two engaged
    assert "sort[" in str(jax.make_jaxpr(compact)(*args))
    assert "sort[" not in str(jax.make_jaxpr(dense)(*args))
    assert _compact_tables() - before == 1          # counted once a trainer
    reg = telemetry.get_registry().snapshot()
    assert reg["local.compact_rows"] == STEPS * BATCH * SEQ
    assert reg["local.compact_rows_of"] == VOCAB
    idx = jax.vmap(lambda t: local_lib._batch_indices(key, t, BATCH, len(x)))(
        jnp.arange(STEPS))
    _assert_same_result(got, want, np.asarray(x)[np.asarray(idx)])


def test_batch_indices_same_outside_and_inside_the_scan():
    key = prng.client_round_key(prng.experiment_key(5), 7, 2)
    count = jnp.asarray(37)
    outside = jax.jit(jax.vmap(
        lambda t: local_lib._batch_indices(key, t, BATCH, count)))(jnp.arange(6))
    _, inside = jax.lax.scan(
        lambda c, t: (c, local_lib._batch_indices(key, t, BATCH, count)),
        0, jnp.arange(6))
    np.testing.assert_array_equal(outside, inside)
    assert 0 <= int(outside.min()) and int(outside.max()) < 37


def test_working_set_under_vmap_over_clients():
    xs, ys = _text_problem(seed=1, clients=3)
    params = _bert().init(jax.random.PRNGKey(1), xs[0, :2])["params"]
    opt = local_lib.make_optimizer(0.01, 0.0, "adam")
    keys = jnp.stack([prng.client_round_key(prng.experiment_key(1), i, 0)
                      for i in range(3)])
    counts, budgets = jnp.asarray([40, 25, 9]), jnp.asarray([STEPS, 2, STEPS])
    run = lambda model: jax.jit(jax.vmap(                      # noqa: E731
        local_lib.make_local_update(model.apply, opt, STEPS, BATCH),
        in_axes=(None, 0, 0, 0, 0, 0)))(params, xs, ys, counts, keys, budgets)
    got, want = run(_bert()), run(_bert(DenseBert))
    for i in range(3):
        idx = jax.vmap(lambda t: local_lib._batch_indices(
            keys[i], t, BATCH, counts[i]))(jnp.arange(STEPS))
        _assert_same_result(pytrees.tree_index(got, i), pytrees.tree_index(want, i),
                            np.asarray(xs[i])[np.asarray(idx)])
    # Different shards, different rows.
    tables = np.asarray(got.delta[TABLE[0]][TABLE[1]])
    assert len({tuple(np.flatnonzero(np.abs(t).sum(1))) for t in tables}) == 3


@pytest.mark.parametrize("case", ["adamw", "scaffold", "grad_sync_axes",
                                  "param_axes", "k_at_least_v",
                                  "no_declaration", "float_input"])
def test_dense_path_where_the_working_set_would_not_be_the_same(case):
    vocab = STEPS * BATCH * SEQ if case == "k_at_least_v" else VOCAB
    model = _bert(DenseBert if case == "no_declaration" else BertClassifier, vocab)
    x, y = _text_problem(vocab=vocab)
    params = model.init(jax.random.PRNGKey(0), x[:2])["params"]
    opt = (optax.adamw(0.01) if case == "adamw"
           else local_lib.make_optimizer(0.01, 0.0, "sgd"))
    kw = {"scaffold": dict(scaffold=True, lr=0.01),
          "grad_sync_axes": dict(grad_sync_axes=("seq",)),
          "param_axes": dict(param_axes=("model",))}.get(case, {})
    apply_fn = model.apply
    if case == "float_input":
        mlp = MLP(num_classes=3, hidden_dim=16, depth=1)
        x, y = _toy_problem()
        params = mlp.init(jax.random.PRNGKey(0), x[:4])["params"]
        apply_fn = mlp.apply
    before = _compact_tables()
    update = local_lib.make_local_update(apply_fn, opt, STEPS, BATCH, **kw)
    args = [params, x, y, jnp.asarray(len(x)), prng.experiment_key(0),
            jnp.asarray(STEPS)]
    if case == "scaffold":
        zeros = pytrees.tree_zeros_like(params)
        args += [zeros, zeros]
    if case == "grad_sync_axes":
        update = jax.vmap(update, in_axes=(None, 0, 0, None, None, None),
                          axis_name="seq")
        args[1], args[2] = args[1][None], args[2][None]
    jaxpr = jax.make_jaxpr(update)(*args)
    assert _compact_tables() == before
    assert "sort[" not in str(jaxpr)                 # no ids were compacted
    out = jax.eval_shape(update, *args)
    delta = (out.result if case == "scaffold" else out).delta
    assert all(jax.tree.leaves(jax.tree.map(
        lambda d, p: d.shape[d.ndim - p.ndim:] == p.shape, delta, params)))


def _parent_local_update(apply_fn, optimizer, num_steps, batch_size):
    """``make_local_update`` as it stood before the working set (its plain
    path: no prox, no scaffold, no grad sync), to compare programs with."""
    def loss_fn(params, global_params, xb, yb):
        logits = apply_fn({"params": params}, xb, train=True)
        return losses.softmax_cross_entropy(logits, yb) + 0.0

    grad_fn = jax.value_and_grad(loss_fn)

    def local_update(global_params, x, y, count, key, step_budget):
        opt_state = optimizer.init(global_params)
        safe_count = jnp.maximum(count, 1)

        def step(carry, t):
            params, opt_state = carry
            k = jax.random.fold_in(key, t)
            idx = jax.random.randint(k, (batch_size,), 0, safe_count)
            xb = jnp.take(x, idx, axis=0)
            yb = jnp.take(y, idx, axis=0)
            loss, grads = grad_fn(params, global_params, xb, yb)
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            active = t < step_budget
            params = jax.tree.map(lambda a, b: jnp.where(active, a, b),
                                  new_params, params)
            opt_state = jax.tree.map(lambda a, b: jnp.where(active, a, b),
                                     new_opt_state, opt_state)
            return (params, opt_state), loss * active

        (params, _), step_losses = jax.lax.scan(
            step, (global_params, opt_state), jnp.arange(num_steps))
        executed = jnp.minimum(step_budget, num_steps).astype(jnp.float32)
        mean_loss = jnp.sum(step_losses) / jnp.maximum(executed, 1.0)
        return local_lib.LocalResult(
            delta=pytrees.tree_sub(params, global_params),
            num_examples=count.astype(jnp.int32),
            completed=step_budget >= max(1, int(num_steps * 0.25)),
            mean_loss=mean_loss, steps_run=executed)

    return local_update


@pytest.mark.parametrize("family", ["cnn", "bert_without_declaration"])
def test_program_without_a_working_set_is_the_parents(family):
    if family == "cnn":
        model = CNN(num_classes=10, width=8)
        x = jnp.zeros((12, 16, 16, 3), jnp.float32)
    else:
        model = _bert(DenseBert)
        x, _ = _text_problem(n=12)
    y = jnp.zeros((12,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x[:2])["params"]
    opt = local_lib.make_optimizer(0.05, 0.9, "sgd")
    args = (params, x, y, jnp.asarray(12), prng.experiment_key(0), jnp.asarray(2))
    new = jax.make_jaxpr(
        local_lib.make_local_update(model.apply, opt, STEPS, BATCH))(*args)
    old = jax.make_jaxpr(
        _parent_local_update(model.apply, opt, STEPS, BATCH))(*args)
    assert str(new) == str(old)
