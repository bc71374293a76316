"""The latent-attention decoder on a widened residual path
(models/xing4.py): the stream maps (doubly stochastic, the rows' sum kept,
the positions-last layout against the written-out one), the gated share of
a mixture (the plain loop over held experts, the shares of all chips
adding up to the uncut layer), latent attention's two cores, the model
against the benchmark's plain reference with and without the prediction
module, its gauges, and ``fit()``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import xing4 as reference
from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.data import registry as data_registry
from colearn_federated_learning_tpu.fed import FederatedLearner, losses
from colearn_federated_learning_tpu.models import mhc, mla, moe, registry
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    RunConfig,
    get_config,
)
from tests.test_nemotron_h import assert_as_with_the_forms_replaced

TINY = dict(name="xing4", num_classes=96, vocab_size=96, width=32,
            seq_len=64, depth=3, dense_layers=1, hc_streams=4,
            sinkhorn_iters=20, sinkhorn_eps=1e-6, res_clamp_min=-30.0,
            res_clamp_max=30.0, num_heads=4, q_rank=12, kv_rank=8,
            nope_dim=8, rope_dim=4, v_dim=8, rope_theta=10000.0,
            yarn_factor=64.0, yarn_original_max=16, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, yarn_mscale_all_dim=1.0, ffn_dim=48,
            num_experts=16, experts_first=4, experts_held=4,
            experts_per_token=4, expert_dim=24, shared_expert_dim=24,
            routed_scale=2.0, mtp_modules=1, norm_eps=1e-6,
            attn_impl="flash")


def _snapshot():
    return telemetry.get_registry().snapshot()


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# --- the stream maps ----------------------------------------------------------


def sinkhorn_written_out(m, iters, eps):
    """``m``: (T, n, n), the token in front: rows then columns, ``iters``
    times."""
    for _ in range(iters):
        m = m / (jnp.einsum("tij->ti", m)[:, :, None] + eps)
        m = m / (jnp.einsum("tij->tj", m)[:, None, :] + eps)
    return m


def _extreme_scores(kind: str):
    """(2, 4, 4, 64) scores far outside the clamp of +-30, or mild."""
    if kind == "mild":
        return jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 64))
    if kind in ("all_high", "all_low"):
        return jnp.full((2, 4, 4, 64), 1e4 if kind == "all_high" else -1e4)
    # One entry a row and column far above the clamp, the others far below.
    pattern = jnp.eye(4)[jnp.array([2, 0, 3, 1])]
    return jnp.broadcast_to((2e4 * pattern - 1e4)[None, :, :, None],
                            (2, 4, 4, 64))


@pytest.mark.parametrize("kind", ["mild", "all_high", "all_low",
                                  "permutation"])
def test_sinkhorn_maps_are_doubly_stochastic(kind):
    """Rows and columns sum to 1 within 1e-4 after 20 iterations, for
    scores the clamp of +-30 has to hold too: all of them at the clamp's
    top or bottom give the uniform map, one a row and column above and the
    others below give that permutation."""
    m = mhc.sinkhorn(
        jnp.exp(jnp.clip(_extreme_scores(kind), -30.0, 30.0)), 20, 1e-6)
    assert m.shape == (2, 4, 4, 64) and bool(jnp.isfinite(m).all())
    assert bool((m >= 0).all())
    np.testing.assert_allclose(m.sum(axis=2), 1.0, atol=1e-4)   # rows
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-4)   # columns
    if kind.startswith("all"):
        np.testing.assert_allclose(m, 0.25, atol=1e-4)
    elif kind == "permutation":
        np.testing.assert_allclose(
            m[0, :, :, 0], jnp.eye(4)[jnp.array([2, 0, 3, 1])], atol=1e-4)


def test_positions_last_layout_is_the_written_out_einsum():
    """``sinkhorn`` on (n, n, T) and the stream's reads and writes as sums
    of slices against (T, n, n) maps and plain einsums: values and
    gradients."""
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    scores = 2.0 * jax.random.normal(keys[0], (1, 4, 4, 48))
    pre = jax.nn.sigmoid(jax.random.normal(keys[1], (1, 4, 48)))
    x = jax.random.normal(keys[2], (1, 4, 48, 16))
    out = jax.random.normal(keys[3], (1, 48, 16))

    def ours(scores, pre, x, out):
        res = mhc.sinkhorn(jnp.exp(scores), 20, 1e-6)
        u = mhc.read_stream(x, pre)
        return jnp.sum(jnp.sin(mhc.write_stream(x, res, 2.0 * pre, out + u)))

    def plain(scores, pre, x, out):
        res = sinkhorn_written_out(
            jnp.exp(jnp.moveaxis(scores[0], -1, 0)), 20, 1e-6)   # (T, n, n)
        rows = jnp.moveaxis(x[0], 0, 1)                          # (T, n, C)
        u = jnp.einsum("jt,tjc->tc", pre[0], rows)
        new = (jnp.einsum("tij,tjc->tic", res, rows)
               + 2.0 * pre[0].T[:, :, None] * (out[0] + u)[:, None, :])
        return jnp.sum(jnp.sin(new))

    args = (scores, pre, x, out)
    got, got_g = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2, 3)))(
        *args)
    want, want_g = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2, 3)))(
        *args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, w in zip(("scores", "pre", "x", "out"), got_g, want_g):
        assert _rel(g, w) < 1e-5, name


def test_mixing_keeps_the_sum_of_the_rows():
    """``sum_i (H_res X)[i] = sum_j X[j]``: the columns of ``H_res`` sum
    to one."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 32, 16))
    maps = mhc.StreamMaps()
    params = maps.init(jax.random.PRNGKey(3), x)["params"]
    # Away from their initial values, so that the map is no identity.
    params = dict(params, gates=jnp.array([0.5, -0.7, 3.0]))
    pre, post, res = maps.apply({"params": params}, x)
    assert pre.shape == post.shape == (2, 4, 32) and res.shape == (2, 4, 4, 32)
    assert float(jnp.abs(res - jnp.eye(4)[None, :, :, None]).max()) > 0.1
    mixed = mhc.write_stream(x, res, jnp.zeros_like(post),
                             jnp.zeros((2, 32, 16)))
    np.testing.assert_allclose(mixed.sum(axis=1), x.sum(axis=1), atol=2e-5)


def test_maps_start_near_the_identity():
    """``H_pre`` at 1/n a row, ``H_post`` at 1, ``H_res`` near the
    identity: a sublayer starts as ``x + F(mean of the rows)``."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 4, 16, 32))
    maps = mhc.StreamMaps()
    pre, post, res = maps.apply(maps.init(jax.random.PRNGKey(5), x), x)
    np.testing.assert_allclose(pre, 0.25, atol=0.01)
    np.testing.assert_allclose(post, 1.0, atol=0.02)
    assert float(jnp.abs(res - jnp.eye(4)[None, :, :, None]).max()) < 0.1


# --- latent attention ---------------------------------------------------------


def test_yarn_frequencies_join_the_kept_and_the_divided():
    plain = mla.yarn_frequencies(64, 10000.0, 1.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(32) / 32),
                               rtol=1e-6)
    got = mla.yarn_frequencies(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    # The fastest turn as they did, the slowest 64 times slower, and the
    # ramp between falls from one to the other.
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[-5:], plain[-5:] / 64, rtol=1e-6)
    ratio = got / plain
    assert (np.diff(ratio) <= 1e-7).all() and 1 / 64 < ratio[16] < 1
    assert mla.yarn_scale(64.0, 1.0) == pytest.approx(2.0048, rel=1e-4)
    assert mla.yarn_scale(1.0, 1.0) == 1.0


def test_latent_attention_cores_agree():
    """The flash kernel (scores over 12, values of 8, yarn's scale) and
    the written-out scores; the parameters are the low-rank maps'."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    outs = {}
    for impl in mla.MLA_IMPLS:
        layer = mla.LatentAttention(
            num_heads=4, q_rank=12, kv_rank=8, nope_dim=8, rope_dim=4,
            v_dim=8, yarn=(64.0, 16, 32.0, 1.0, 1.0), impl=impl,
            init_std=0.3)
        params = layer.init(jax.random.PRNGKey(1), u)["params"]
        assert jax.tree.map(jnp.shape, params) == {
            "q_a": {"kernel": (32, 12)}, "q_norm": (12,),
            "q_b": {"kernel": (12, 48)}, "kv_a": {"kernel": (32, 12)},
            "kv_norm": (8,), "kv_b": {"kernel": (8, 64)},
            "out": {"kernel": (32, 32)}}
        outs[impl] = layer.apply({"params": params}, u)
    np.testing.assert_allclose(outs["flash"], outs["dense"], atol=1e-5)
    with pytest.raises(ValueError, match="not 'ring'"):
        mla.LatentAttention(num_heads=4, q_rank=12, kv_rank=8, nope_dim=8,
                            rope_dim=4, v_dim=8, impl="ring",
                            yarn=(1.0, 16, 32.0, 1.0, 1.0)).init(
            jax.random.PRNGKey(1), u)


# --- the gated share ----------------------------------------------------------


def _share_layer(first=4, count=4, total=16, top_k=4, row_tile=16, **kw):
    return moe.GatedMoEShare(
        embed_dim=32, expert_dim=24, shared_dim=40, experts_total=total,
        experts_held=(first, count), top_k=top_k, routed_scale=2.0,
        init_std=0.3, row_tile=row_tile, **kw)


MODEL = dict(experts_per_token=4, routed_scale=2.0, experts_first=4)


def _program_and_reference(layer):
    def program(p, u):
        return jnp.sum(jnp.sin(layer.apply({"params": p}, u)))

    def plain(p, u):
        return jnp.sum(jnp.sin(reference.moe(u, p, MODEL)))

    return [jax.value_and_grad(f, argnums=(0, 1)) for f in (program, plain)]


# The correction bias on the held experts: 0 leaves the router as drawn, +10
# sends every token to all of them (the rows' static bound is met), -10 none.
@pytest.mark.parametrize("biases", [(0.0,), (10.0,), (-10.0,), (10.0, 0.0)],
                         ids=["uniform", "every_held", "none_held",
                              "vmap_bound_and_uniform"])
def test_gated_share_is_the_plain_loop_over_held_experts(biases):
    """Answer and every gradient leaf against the reference's loop over
    the held experts: under the router as drawn, at the bound (every token
    on every held expert) and with an empty share (the shared expert alone
    answers; the banks' gradients are 0 and finite), for two clients with
    different counts under one ``vmap`` too; tokens in two blocks."""
    first, count = 4, 4
    layer = _share_layer(token_block=32)
    us = jax.random.normal(jax.random.PRNGKey(0), (len(biases), 64, 32))
    drawn = layer.init(jax.random.PRNGKey(1), us[0])["params"]
    assert set(drawn) == {
        "router", "router_bias", "experts_gate", "experts_up",
        "experts_down", "shared_gate", "shared_up", "shared_down"}
    assert drawn["experts_gate"].shape == (4, 32, 24)
    assert drawn["experts_down"].shape == (4, 24, 32)
    clients = [dict(drawn, router_bias=drawn["router_bias"].at[
        first:first + count].set(bias)) for bias in biases]
    run = _program_and_reference(layer)
    if len(biases) == 1:
        args = (clients[0], us[0])
    else:
        run = [jax.vmap(f) for f in run]
        args = (jax.tree.map(lambda *a: jnp.stack(a), *clients), us)
    (got, got_g), (want, want_g) = (jax.jit(f)(*args) for f in run)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-4)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        name = jax.tree_util.keystr(path)
        assert np.isfinite(g).all(), name
        if "router_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any()
        elif biases == (-10.0,) and ("experts" in name
                                     or name.endswith("['router']")):
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
        else:
            assert _rel(g, w) < 2e-5, name


@pytest.mark.parametrize("biases", [(0.0,), (10.0,), (-10.0,), (10.0, 0.0)],
                         ids=["uniform", "every_held", "none_held",
                              "vmap_bound_and_uniform"])
@pytest.mark.parametrize("top_k,count", [(4, 4), (2, 4), (6, 4)],
                         ids=["as_many", "fewer", "more_choices_than_held"])
def test_gated_share_gradients_are_the_replaced_forms(top_k, count, biases,
                                                      monkeypatch):
    """The choice exactly, ``route``'s weights within 4 ulp, the gated
    layer's answer and its gradient in the router, the three banks, the
    shared expert and the input as with the gather of the chosen scores
    and the sort of every pair that ``route`` and ``held_pairs`` were until
    PR 36 (``tests/test_nemotron_h.py`` writes them out), with fewer and
    more choices than held experts."""
    assert_as_with_the_forms_replaced(
        _share_layer(count=count, top_k=top_k, token_block=32), 4, count,
        biases, monkeypatch)


def test_the_shares_of_all_chips_add_up_to_the_uncut_gated_layer():
    """The program's layer, built as one chip's share and given that
    share's slice of an uncut layer's banks, once for each of the 8 shares
    of a mixture of 64 (8 experts each, the published split): the routed
    parts summed, with what every chip computes alike (the router, the
    shared expert) counted once, are the uncut reference's layer, and
    every (token, choice) pair lies on exactly one share."""
    u = jax.random.normal(jax.random.PRNGKey(0), (48, 32))
    sizes = {"router": (32, 64), "router_bias": (64,),
             "experts_gate": (64, 32, 24), "experts_up": (64, 32, 24),
             "experts_down": (64, 24, 32), "shared_gate": (32, 40),
             "shared_up": (32, 40), "shared_down": (40, 32)}
    keys = jax.random.split(jax.random.PRNGKey(1), len(sizes))
    p = {name: 0.4 * jax.random.normal(k, shape)
         for k, (name, shape) in zip(keys, sizes.items())}
    want = reference.moe(u, p, dict(MODEL, experts_first=0))
    parts, pairs = [], 0
    for j in range(8):
        layer = _share_layer(first=8 * j, count=8, total=64)
        own = dict(p, **{name: p[name][8 * j:8 * j + 8] for name in (
            "experts_gate", "experts_up", "experts_down")})
        parts.append(layer.apply({"params": own}, u, u, method="routed_part"))
        chosen, weights = layer.apply({"params": own}, u, method="route")
        pairs += int(moe.held_pairs(chosen, weights, 8 * j, 8)[3].sum())
    assert pairs == 48 * 4
    shared = layer.apply({"params": own}, u, method="shared")
    assert _rel(sum(parts) + shared, want) < 1e-5


# --- the model ----------------------------------------------------------------


def _model_and_batch(**changes):
    config = {**TINY, **changes}
    model = registry.build_model(ModelConfig(**config))
    heads = 1 + config["mtp_modules"]
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 64, heads), 0, 96)
    params = registry.init_params(model, ids[:1], jax.random.PRNGKey(0))

    def moved(path, a):
        """Matrices enlarged, so that the sublayers weigh against the
        embedding; the maps' gates and biases away from their start, so
        that the streams differ and mix."""
        name = jax.tree_util.keystr(path)
        if "_maps" in name and ("gates" in name or "bias" in name):
            return a + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), a.shape)
        return 4.0 * a if a.ndim >= 2 else a

    return (model, jax.tree_util.tree_map_with_path(moved, params), ids, y,
            config)


@pytest.mark.parametrize("modules", [1, 0], ids=["mtp", "no_mtp"])
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_model_matches_the_plain_reference(impl, modules):
    """The loss (to 1e-5) and every gradient leaf (to 1e-3), float32
    against float32, with both attention cores, with the prediction module
    and without it; the kernel's cases rematerialise their layers, as the
    shipped configuration does."""
    model, params, ids, y, config = _model_and_batch(
        attn_impl=impl, mtp_modules=modules, remat=impl == "flash")

    def program(p):
        return losses.softmax_cross_entropy(
            model.apply({"params": p}, ids, train=True), y)

    loss, grads = jax.jit(jax.value_and_grad(program))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, y, config)))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    assert ("mtp_layer" in params) == bool(modules)
    for (path, want), got in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree.leaves(grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(got).any(), name
        else:
            assert _rel(got, want) < 1e-3, name


@pytest.mark.parametrize("modules", [1, 0], ids=["mtp", "no_mtp"])
def test_model_logits_and_the_references(modules):
    model, params, ids, _, config = _model_and_batch(mtp_modules=modules)
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 64, 1 + modules, 96)
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(
        logits, reference.forward(params, ids, config), atol=2e-5)


@pytest.mark.parametrize("position", [1, 17, 40])
def test_model_is_causal_and_the_module_looks_one_further(position):
    """A change at a position leaves the first head's logits before it as
    they were, and the prediction module's before the position in front of
    it: the module at ``t`` reads the token at ``t + 1``."""
    model, params, ids, _, _ = _model_and_batch()
    before = model.apply({"params": params}, ids)
    changed = ids.at[:, position].set((ids[:, position] + 1) % 96)
    after = model.apply({"params": params}, changed)
    np.testing.assert_allclose(before[:, :position, 0],
                               after[:, :position, 0], atol=1e-6)
    np.testing.assert_allclose(before[:, :position - 1, 1],
                               after[:, :position - 1, 1], atol=1e-6)
    assert not np.allclose(before[:, position:, 0], after[:, position:, 0])
    assert not np.allclose(before[:, position - 1, 1],
                           after[:, position - 1, 1])


def test_gauges_say_what_was_built():
    _model_and_batch(depth=1, dense_layers=1, mtp_modules=0)
    got = _snapshot()                        # set on every build
    assert got["xing4.layers{kind=moe}"] == 0 and got["mtp.modules"] == 0
    _model_and_batch()
    got = _snapshot()
    assert got["xing4.layers{kind=dense}"] == 1
    assert got["xing4.layers{kind=moe}"] == 2
    assert (got["mhc.streams"], got["mhc.sinkhorn_iters"]) == (4, 20)
    assert (got["mla.heads"], got["mla.qk_dim"], got["mla.v_dim"],
            got["mla.kv_rank"]) == (4, 12, 8, 8)
    assert got["mtp.modules"] == 1
    assert (got["moe.experts_held"], got["moe.experts_total"],
            got["moe.top_k"]) == (4, 16, 4)
    # A block's sort: its membership table, 4 held experts x 64 tokens.
    assert got["moe.pair_sort_keys"] == 4 * 64


def test_another_family_sets_none_of_the_gauges(monkeypatch):
    """A fresh registry, a ``nemotron_h`` build: the hybrid stack's gauges
    are there, the widened path's, latent attention's and the prediction
    module's are not."""
    from colearn_federated_learning_tpu.telemetry import registry as metrics

    monkeypatch.setattr(metrics, "_default_registry",
                        metrics.MetricsRegistry())
    model = registry.build_model(ModelConfig(
        name="nemotron_h", num_classes=96, vocab_size=96, width=32,
        seq_len=64, layer_pattern="ME*", mamba_heads=4, mamba_head_dim=8,
        mamba_groups=2, ssm_state_size=8, chunk_size=16, num_experts=16,
        experts_first=4, experts_held=4, experts_per_token=6, latent_dim=16,
        expert_dim=24, shared_expert_dim=40, num_heads=4, num_kv_heads=2,
        head_dim=8, attn_impl="dense"))
    registry.init_params(model, jnp.zeros((1, 64), jnp.int32),
                         jax.random.PRNGKey(0))
    got = _snapshot()
    assert got["hybrid.layers{kind=moe}"] == 1 and got["moe.experts_held"] == 4
    assert not [k for k in got if k.startswith(
        ("mhc.", "mla.", "mtp.", "xing4."))]


def test_registry_guards_name_the_family():
    with pytest.raises(ValueError, match="leading dense layers"):
        _model_and_batch(dense_layers=4)
    with pytest.raises(ValueError, match="one prediction module or none"):
        _model_and_batch(mtp_modules=2)
    with pytest.raises(ValueError, match="not 'ring'"):
        registry.build_model(ModelConfig(**{**TINY, "attn_impl": "ring"}))
    with pytest.raises(ValueError, match="not 'xing4'"):
        registry.build_model(ModelConfig(**TINY), seq_axis_name="seq")
    shipped = get_config("xing4_fedavg").model
    assert shipped.remat and (shipped.width, shipped.depth) == (3584, 5)
    built = registry.build_model(shipped)
    assert built.experts_held == (0, 8) and built.yarn[0] == 64.0


def test_tokens_ahead_labels_two_tokens_a_position():
    data = data_registry.get_dataset("tokens_ahead_tiny", seed=3)
    x, y = data.x_train, data.y_train
    assert x.shape == (64, 64) and y.shape == (64, 64, 2)
    assert data.y_test.shape == (8, 64, 2) and x.dtype == y.dtype == np.int32
    np.testing.assert_array_equal(y[:, :-1, 0], x[:, 1:])
    np.testing.assert_array_equal(y[:, :-1, 1], y[:, 1:, 0])
    assert 0 <= y.min() and y.max() < 96 and (x == 0).any()
    again = data_registry.get_dataset("tokens_ahead_tiny", seed=3)
    np.testing.assert_array_equal(again.y_train, y)
    # The next-token stream is untouched by the horizon's option.
    plain = data_registry.get_dataset("tokens_tiny", seed=3)
    assert plain.y_train.shape == (64, 64)


def _experiment(**model):
    shipped = get_config("xing4_fedavg")
    return ExperimentConfig(
        data=DataConfig(dataset="tokens_ahead_tiny", num_clients=4,
                        partition="iid"),
        model=dataclasses.replace(
            shipped.model, **{**TINY, "dtype": "float32", **model}),
        fed=dataclasses.replace(shipped.fed, cohort_size=1, lr=0.1),
        run=RunConfig(name="xing4_tiny", eval_every=1))


def test_fit_trains_and_evaluates_over_two_heads():
    """Two rounds through ``FederatedLearner.from_config`` with an
    evaluation after each: every record is evaluated per token over both
    heads, the loss falls, one round program was built."""
    learner = FederatedLearner.from_config(_experiment())
    records = learner.fit(rounds=2)
    assert len(records) == 2
    assert all(np.isfinite(r["train_loss"]) and "eval_loss" in r
               and 0.0 <= r["eval_acc"] <= 1.0 for r in records)
    assert records[0]["train_loss"] == pytest.approx(np.log(96), rel=0.05)
    assert records[-1]["eval_loss"] < records[0]["eval_loss"] < np.log(96)
    loss, _ = learner.evaluate()
    assert loss == pytest.approx(records[-1]["eval_loss"])
    assert learner._round_fn.compiles == 1
    after = _snapshot()
    assert after["mhc.streams"] == 4 and after["mtp.modules"] == 1
