"""The causal byte-level client (models/evabyte.py): the flash kernel's
``prefix``, EVA attention on it against the written-out scores and a
per-query loop, the model against the benchmark's plain reference,
causality, a loss and an evaluation per token, and ``fit()`` on the vmap
and mesh paths."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmarks.reference import evabyte as reference
from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.data import registry as data_registry
from colearn_federated_learning_tpu.fed import FederatedLearner, losses
from colearn_federated_learning_tpu.fed.evaluation import (
    eval_rows,
    make_eval_fn,
)
from colearn_federated_learning_tpu.models import registry
from colearn_federated_learning_tpu.ops.attention import flash_attention
from colearn_federated_learning_tpu.ops.eva import eva_attention
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    RunConfig,
    get_config,
)

_NEG = -1e30
TINY = dict(name="evabyte", num_classes=320, vocab_size=320, width=32,
            depth=2, num_heads=2, seq_len=128, ffn_dim=48, window_size=32,
            chunk_size=4, num_pred_heads=8, rope_theta=100000.0)


def _qkv(key, B, Lq, Lk, H=2, D=8):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, Lq, H, D), jnp.float32),
            jax.random.normal(ks[1], (B, Lk, H, D), jnp.float32),
            jax.random.normal(ks[2], (B, Lk, H, D), jnp.float32))


def prefix_oracle(q, k, v, kv_mask, prefix):
    """Every query sees the first ``prefix`` keys the mask leaves, and key
    ``prefix + j`` for ``j`` up to its own position."""
    Lq, Lk = q.shape[1], k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    seen = (jnp.arange(Lk)[None, :] < prefix) | (
        jnp.arange(Lq)[:, None] >= jnp.arange(Lk)[None, :] - prefix)
    seen = seen[None, None] & kv_mask[:, None, None, :]
    p = jax.nn.softmax(jnp.where(seen, logits, _NEG), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("prefix", [0, 8, 5])
def test_flash_prefix_matches_the_oracle(prefix, vmapped):
    """Forward and all three gradients; blocks of 8, so a prefix of none,
    of one block, and of part of one; a key hidden inside the prefix and
    one after it.  Every case's backward reads the log-sum that the
    forward left dense (``_flash_fwd``), with a mask and with none
    hidden (row 0 of prefix 0), plain and mapped: what holds it there
    needs no case of its own."""
    B, Lq = 2, 24
    q, k, v = _qkv(jax.random.PRNGKey(prefix), B, Lq, prefix + Lq)
    mask = jnp.ones((B, prefix + Lq), bool).at[1, prefix + 3].set(False)
    if prefix:
        mask = mask.at[0, 1].set(False).at[1, :prefix].set(False)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, causal=True, prefix=prefix,
                               block_q=8, block_k=8)

    def oracle(q, k, v):
        return prefix_oracle(q, k, v, mask, prefix)

    weight = jnp.cos(jnp.arange(8.0))
    args = (q, k, v)
    if vmapped:     # a client axis in front, as the round program has
        flash, oracle = jax.vmap(flash), jax.vmap(oracle)
        args = tuple(jnp.stack([a, 2.0 * a]) for a in args)

    def run(fn):
        return fn(*args), jax.grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2))(*args)

    out, grads = run(flash)
    want, want_grads = run(oracle)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_flash_prefix_needs_causal():
    q, k, v = _qkv(jax.random.PRNGKey(0), 1, 8, 12)
    with pytest.raises(ValueError, match="needs causal=True"):
        flash_attention(q, k, v, prefix=4)


def eva_by_query(q, k, v, mu, phi, window, chunk):
    """The layer's equations, one query at a time, in numpy."""
    q, k, v, mu, phi = (np.asarray(a, np.float64) for a in (q, k, v, mu, phi))
    B, L, H, D = q.shape
    s = D ** -0.5
    out = np.zeros_like(q)

    def softmax(a):
        e = np.exp(a - a.max())
        return e / e.sum()

    for b in range(B):
        for h in range(H):
            ks = np.stack([softmax(s * k[b, c:c + chunk, h] @ mu[h])
                           @ k[b, c:c + chunk, h]
                           for c in range(0, L, chunk)])
            vs = np.stack([softmax(s * k[b, c:c + chunk, h] @ phi[h])
                           @ v[b, c:c + chunk, h]
                           for c in range(0, L, chunk)])
            for i in range(L):
                start = i // window * window
                keys = np.concatenate(
                    [k[b, start:i + 1, h], ks[:start // chunk]])
                values = np.concatenate(
                    [v[b, start:i + 1, h], vs[:start // chunk]])
                out[b, i, h] = softmax(s * keys @ q[b, i, h]) @ values
    return out


def _eva_inputs(L=48, H=2, D=8):
    q, k, v = _qkv(jax.random.PRNGKey(7), 2, L, L, H, D)
    mu = jax.random.normal(jax.random.PRNGKey(8), (H, D))
    phi = jax.random.normal(jax.random.PRNGKey(9), (H, D))
    return q, k, v, mu, phi


@pytest.mark.parametrize("impl", ["flash", "dense"])
def test_eva_attention_matches_the_equations(impl):
    args = _eva_inputs()
    got = eva_attention(*args, window=16, chunk=4, impl=impl)
    np.testing.assert_allclose(
        got, eva_by_query(*args, window=16, chunk=4), rtol=1e-4, atol=1e-5)


def test_eva_attention_flash_gradients_match_dense():
    args = _eva_inputs()

    def loss(impl):
        return lambda *a: jnp.sum(jnp.sin(
            eva_attention(*a, window=16, chunk=4, impl=impl)))

    for got, want in zip(jax.grad(loss("flash"), argnums=range(5))(*args),
                         jax.grad(loss("dense"), argnums=range(5))(*args)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_eva_attention_one_window_is_causal_attention():
    from colearn_federated_learning_tpu.parallel.ring import dense_attention

    q, k, v, mu, phi = _eva_inputs(L=16)
    got = eva_attention(q, k, v, mu, phi, window=2048, chunk=4)
    np.testing.assert_allclose(got, dense_attention(q, k, v, causal=True),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="whole windows of whole chunks"):
        eva_attention(q, k, v, mu, phi, window=12, chunk=4)


def _model_and_batch(attn_impl="flash", scale=8.0, **sizes):
    sizes = {**TINY, **sizes, "attn_impl": attn_impl}
    model = registry.build_model(ModelConfig(**sizes))
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, sizes["seq_len"]),
                             0, 320)
    y = jax.random.randint(jax.random.PRNGKey(1),
                           (2, sizes["seq_len"], 8), 0, 320)
    params = registry.init_params(model, ids, jax.random.PRNGKey(2))
    # The published init leaves the attention near uniform at this width;
    # larger weights make every term of the layer count.
    params = jax.tree.map(lambda a: a * scale, params)
    return model, params, ids, y, sizes


def _loss_fn(model, ids, y):
    return lambda p: losses.softmax_cross_entropy(
        model.apply({"params": p}, ids, train=True), y)


@pytest.mark.parametrize("attn_impl", ["flash", "dense"])
def test_model_matches_the_plain_reference(attn_impl):
    """Loss and every gradient leaf, float32, tight."""
    model, params, ids, y, sizes = _model_and_batch(attn_impl)
    loss, grads = jax.value_and_grad(_loss_fn(model, ids, y))(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, ids, y, sizes))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(ref_grads)):
        gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def test_model_logits_and_remat():
    model, params, ids, _, sizes = _model_and_batch()
    logits = model.apply({"params": params}, ids, train=False)
    assert logits.shape == (2, 128, 8, 320) and logits.dtype == jnp.float32
    remat = registry.build_model(ModelConfig(**{**sizes, "remat": True}))
    np.testing.assert_allclose(
        remat.apply({"params": params}, ids, train=True), logits,
        rtol=1e-6, atol=1e-6)
    bf16 = registry.build_model(ModelConfig(**{**sizes, "dtype": "bfloat16"}))
    assert bf16.apply({"params": params}, ids).dtype == jnp.float32
    with pytest.raises(ValueError, match="evabyte's attention runs as"):
        registry.build_model(ModelConfig(**{**sizes, "attn_impl": "ring"}))


def kernel_calls(fn, *args):
    """The flash kernels' calls in the jaxpr of ``fn`` at ``args``."""
    return collections.Counter(re.findall(
        r"\bname=(flash_(?:fwd|dq|dkv))\b", str(jax.make_jaxpr(fn)(*args))))


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_a_rematerialised_block_runs_the_forward_kernel_once(remat):
    """Depth 2: one ``flash_fwd`` a layer with ``remat`` too (plain
    ``nn.remat`` traced 4), because the block keeps the kernel's output
    and log-sum; the backward's two kernels once a layer either way."""
    model, params, ids, y, _ = _model_and_batch(remat=remat)
    assert kernel_calls(jax.grad(_loss_fn(model, ids, y)), params) == {
        "flash_fwd": 2, "flash_dq": 2, "flash_dkv": 2}


@pytest.mark.parametrize("vmapped", [False, True], ids=["jit", "vmap"])
def test_remat_keeps_loss_and_gradients(vmapped):
    """What is kept and what is computed again are the same numbers:
    float32 rounding apart (XLA orders a recomputed sum as it likes); with
    a client axis in front, as ``fed/programs.py`` maps it."""
    results = []
    for remat in (False, True):
        model, params, ids, y, _ = _model_and_batch(remat=remat)

        def run(p, ids, y, model=model):
            return jax.value_and_grad(_loss_fn(model, ids, y))(p)

        args = (params, ids, y)
        if vmapped:
            run = jax.vmap(run)
            args = (jax.tree.map(lambda a: jnp.stack([a, 0.5 * a]), params),
                    jnp.stack([ids, ids[::-1]]), jnp.stack([y, y[::-1]]))
        results.append(jax.jit(run)(*args))
    (loss, grads), (remat_loss, remat_grads) = results
    np.testing.assert_allclose(remat_loss, loss, rtol=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(remat_grads)
    for (path, want), got in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree.leaves(remat_grads)):
        gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert gap < 1e-5, (jax.tree_util.keystr(path), gap)


@pytest.mark.parametrize("remat,saved", [(False, 0), (True, 2)],
                         ids=["kept", "remat"])
def test_gauge_counts_what_a_rematerialised_block_keeps(remat, saved):
    _model_and_batch(remat=not remat)       # the gauge is set on every build
    _model_and_batch(remat=remat)
    assert _snapshot()["evabyte.remat_saved_arrays"] == saved


def test_plain_remat_of_a_flash_block_still_repeats_the_kernel():
    """The two names change nothing for a caller whose ``nn.remat`` has
    no policy (``models/bert.py``): its backward runs ``flash_fwd`` again,
    2 × depth calls in all, and the numbers are ``remat=False``'s."""
    sizes = dict(name="bert", num_classes=4, width=32, depth=2, num_heads=4,
                 seq_len=64, vocab_size=2000, attn_impl="flash")
    x = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 1, 2000)
    y = jax.random.randint(jax.random.PRNGKey(2), (4,), 0, 4)
    results = {}
    for remat in (False, True):
        model = registry.build_model(ModelConfig(**sizes, remat=remat))
        params = registry.init_params(model, x, jax.random.PRNGKey(0))
        loss = _loss_fn(model, x, y)
        assert kernel_calls(jax.grad(loss), params) == {
            "flash_fwd": 4 if remat else 2, "flash_dq": 2, "flash_dkv": 2}
        results[remat] = jax.jit(jax.value_and_grad(loss))(params)
    np.testing.assert_allclose(results[True][0], results[False][0], rtol=1e-6)
    for got, want in zip(jax.tree.leaves(results[True][1]),
                         jax.tree.leaves(results[False][1])):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("position", [31, 32, 35, 36, 100])
def test_model_is_causal(position):
    """A change at a position leaves the logits before it as they were:
    across a window's boundary (32), a chunk's (36) and inside both."""
    model, params, ids, _, _ = _model_and_batch()
    before = model.apply({"params": params}, ids)
    changed = ids.at[:, position].set((ids[:, position] + 1) % 320)
    after = model.apply({"params": params}, changed)
    np.testing.assert_array_equal(before[:, :position], after[:, :position])
    assert not np.allclose(before[:, position:], after[:, position:])


def test_loss_and_accuracy_per_token_against_a_hand_count():
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8, 11))
    y = jax.random.randint(jax.random.PRNGKey(1), (3, 5, 8), 0, 11)
    logp = np.asarray(jax.nn.log_softmax(logits))
    want = -np.mean([logp[n, i, j, y[n, i, j]] for n in range(3)
                     for i in range(5) for j in range(8)])
    assert float(losses.softmax_cross_entropy(logits, y)) == pytest.approx(
        want, rel=1e-6)
    hits = np.mean(np.asarray(logits).argmax(-1) == np.asarray(y))
    assert float(losses.accuracy(logits, y)) == pytest.approx(hits)
    with pytest.raises(ValueError, match="need labels"):
        losses.softmax_cross_entropy(logits, y[..., 0])


def test_eval_fn_per_token_against_a_hand_count():
    """Five rows in batches of two: the padding row counts for nothing."""
    table = jax.random.normal(jax.random.PRNGKey(0), (13, 4 * 7))

    def apply_fn(variables, x, train):
        return (variables["params"][x]).reshape(*x.shape, 4, 7)

    x = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (5, 6), 0, 13))
    y = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (5, 6, 4), 0, 7))
    loss, acc = make_eval_fn(apply_fn, x, y, batch=2)(table)
    logits = np.asarray(table)[x].reshape(5, 6, 4, 7)
    logp = np.asarray(jax.nn.log_softmax(logits))
    picked = np.take_along_axis(logp, y[..., None], axis=-1)
    assert float(loss) == pytest.approx(-picked.mean(), rel=1e-5)
    assert float(acc) == pytest.approx((logits.argmax(-1) == y).mean())


def test_eval_batches_are_bounded_in_tokens():
    images = np.zeros((10, 32, 32, 3), np.float32)
    assert eval_rows(32, images) == 64 and eval_rows(128, images) == 128
    assert eval_rows(16, np.zeros((10, 128), np.int32)) == 64     # BERT
    assert eval_rows(1, np.zeros((4, 16384), np.int32)) == 1
    assert eval_rows(2, np.zeros((4, 16384), np.int32)) == 2
    assert eval_rows(1, np.zeros((4, 1 << 20), np.int32)) == 1


def test_bytes_dataset_labels_every_position():
    data = data_registry.get_dataset("bytes_tiny", seed=3)
    x, y = data.x_train, data.y_train
    assert x.shape == (64, 128) and y.shape == (64, 128, 8)
    assert data.y_test.shape == (8, 128, 8) and x.dtype == y.dtype == np.int32
    for j in range(8):
        np.testing.assert_array_equal(y[:, :-1 - j, j], x[:, 1 + j:])
    assert set(np.unique(x)) <= {3, *range(64, 320)} and (x == 3).any()
    again = data_registry.get_dataset("bytes_tiny", seed=3)
    np.testing.assert_array_equal(again.x_train, x)
    assert (data_registry.get_dataset("bytes_tiny", seed=4).x_train != x).any()


def _experiment(cohort, attn_impl="flash", **fed):
    shipped = get_config("evabyte_fedavg")
    assert shipped.model.width == 4096 and shipped.model.attn_impl == "flash"
    return ExperimentConfig(
        data=DataConfig(dataset="bytes_tiny", num_clients=4, partition="iid"),
        model=dataclasses.replace(
            shipped.model, **{k: v for k, v in TINY.items() if k != "name"},
            dtype="float32", attn_impl=attn_impl),
        fed=dataclasses.replace(shipped.fed, cohort_size=cohort, lr=1.0,
                                **fed),
        run=RunConfig(name="evabyte_tiny", eval_every=1))


def _snapshot():
    return telemetry.get_registry().snapshot()


@pytest.mark.parametrize("devices", [1, 2], ids=["vmap", "mesh2"])
def test_fit_trains_and_evaluates(devices):
    """Three rounds through ``fit()`` with an evaluation after each: the
    loss falls, the kernel path was built (its gauges and counter say so)
    and the working set of rows stayed off."""
    before = _snapshot()
    mesh = None if devices == 1 else Mesh(
        np.array(jax.devices()[:devices]), ("clients",))
    learner = FederatedLearner(_experiment(cohort=devices), mesh=mesh)
    records = learner.fit(rounds=3)
    assert len(records) == 3
    assert all(np.isfinite(r["train_loss"]) for r in records)
    assert records[0]["train_loss"] == pytest.approx(np.log(320), rel=0.01)
    assert records[-1]["eval_loss"] < records[0]["eval_loss"] < 5.8
    assert records[-1]["train_loss"] < records[0]["train_loss"]
    assert 0.0 <= records[-1]["eval_acc"] <= 1.0
    loss, acc = learner.evaluate()
    assert loss == pytest.approx(records[-1]["eval_loss"])
    assert learner._round_fn.compiles == 1
    after = _snapshot()
    assert after["eva.keys_per_query_max"] == 32 + 3 * 8
    assert after["eva.window"] == 32 and after["eva.chunk"] == 4
    assert (after["attention.flash_prefix_calls"]
            > before.get("attention.flash_prefix_calls", 0))
    assert after.get("local.compact_tables", 0) == before.get(
        "local.compact_tables", 0)
    per_client = learner.evaluate_per_client()
    assert per_client["per_client_loss"].shape == (4,)
    assert per_client["weighted_loss"] == pytest.approx(loss, rel=0.2)


def test_dense_fit_agrees_with_flash():
    flash = FederatedLearner(_experiment(cohort=1)).fit(rounds=2)
    dense = FederatedLearner(_experiment(cohort=1, attn_impl="dense")).fit(
        rounds=2)
    for a, b in zip(flash, dense):
        assert a["train_loss"] == pytest.approx(b["train_loss"], rel=1e-4)
        assert a["eval_loss"] == pytest.approx(b["eval_loss"], rel=1e-4)


def test_a_label_per_token_has_no_class_to_partition_by():
    config = _experiment(cohort=1)
    config = config.replace(data=dataclasses.replace(
        config.data, partition="dirichlet"))
    with pytest.raises(ValueError, match="a label per token"):
        FederatedLearner(config)
