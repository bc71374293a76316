"""The hybrid of Kimi delta attention and latent attention with gated
experts (models/ling3.py): which layer gets which mixer and which
feed-forward, the mixer against the benchmark's plain reference (one
position at a time), latent attention's variations on both cores, the
clamp, the model against the reference with and without rematerialisation,
its gauges, and ``fit()``."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ling3 as reference
from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.fed import FederatedLearner, losses
from colearn_federated_learning_tpu.models import ling3, mla, moe, registry
from colearn_federated_learning_tpu.ops import kda
from colearn_federated_learning_tpu.ops.kda_conv import conv_qkv
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    RunConfig,
    get_config,
)

TINY = dict(name="ling3", num_classes=96, vocab_size=96, width=32,
            seq_len=64, depth=7, first_layer=1, layer_group_size=6,
            dense_layers=1, num_heads=4, head_dim=8, conv_kernel=4,
            chunk_size=8, kda_lower_bound=-5.0, kv_rank=8, nope_dim=8,
            rope_dim=4, v_dim=8, rope_theta=6e6, ffn_dim=48, num_experts=16,
            experts_first=4, experts_held=4, experts_per_token=4,
            expert_groups=4, expert_groups_kept=2, expert_dim=24,
            shared_expert_dim=24, routed_scale=2.5, moe_row_tile=16,
            norm_eps=1e-6, attn_impl="flash")
# The published limits are 0 on the layers the cell holds; here two layers'
# experts and two layers' shared experts are clamped.
LIMITS = dict(expert_limits=(0, 0.5, 0, 0, 0.3, 0, 0),
              shared_expert_limits=(0, 0, 0.4, 0, 0, 0, 0.2))


def _snapshot():
    return telemetry.get_registry().snapshot()


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _model_and_batch(**changes):
    config = {**TINY, **changes}
    model = registry.build_model(ModelConfig(**config))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 96)
    params = registry.init_params(model, ids[:1], jax.random.PRNGKey(0))

    def moved(path, a):
        """Away from the initial values, so that every sublayer weighs
        against the embedding, the decays differ by channel and the clamps
        bite."""
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name))
        if a.ndim >= 2:
            return 4.0 * a
        if "A_log" in name or "dt_bias" in name:
            # As drawn, most heads' gates sit where the sigmoid is flat.
            return jax.random.normal(key, a.shape)
        return a + 0.3 * jax.random.normal(key, a.shape)

    return (model, jax.tree_util.tree_map_with_path(moved, params), ids, y,
            config)


# --- which layer gets what ----------------------------------------------------


def test_the_published_pattern():
    """Of the published 42 layers every sixth mixes by latent attention (5,
    11, ... 41) and the others by the delta rule: five to one."""
    kinds = [ling3.mixer_kind(i, 6) for i in range(42)]
    assert [i for i, k in enumerate(kinds) if k == "mla"] == list(
        range(5, 42, 6))
    assert kinds.count("kda") == 35


@pytest.mark.parametrize("first,depth,dense,mixers", [
    (1, 7, 1, "kkkkmkk"),         # the cell's cut: published layers 1-7
    (0, 7, 2, "kkkkkmk"),         # the stack's own start
    (5, 2, 0, "mk"),
])
def test_layers_get_their_mixer_and_feed_forward_by_index(first, depth, dense,
                                                          mixers):
    model, params, ids, _, config = _model_and_batch(
        first_layer=first, depth=depth, dense_layers=dense)
    assert reference.layer_kinds(config) == [
        ({"k": "kda", "m": "mla"}[m], "dense" if j < dense else "moe")
        for j, m in enumerate(mixers)]
    for j, m in enumerate(mixers):
        layer = params[f"layer_{j}"]
        assert ("in_proj" in layer["mixer"]) == (m == "k"), j
        assert ("kv_a" in layer["mixer"]) == (m == "m"), j
        assert ("router" in layer["ffn"]) == (j >= dense), j
        assert (set(layer["ffn"]) == {"gate", "up", "down"}) == (j < dense)
    got = _snapshot()
    assert got["ling3.layers{kind=kda}"] == mixers.count("k")
    assert got["ling3.layers{kind=mla}"] == mixers.count("m")
    assert got["ling3.layers{kind=dense}"] == dense
    assert got["ling3.layers{kind=moe}"] == depth - dense
    assert got["kda.layers"] == mixers.count("k")


# --- the mixers ---------------------------------------------------------------


def test_kda_mixer_is_the_reference_one_position_at_a_time():
    """Answer and every gradient leaf; a length that is no multiple of the
    chunk; the parameters are the fused map of the stream, the taps, the
    decay's, the head norm and the output map."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 27, 32))
    mixer = ling3.KdaMixer(num_heads=4, head_dim=8, conv_kernel=4, chunk=8,
                           lower_bound=-5.0)
    params = mixer.init(jax.random.PRNGKey(1), u)["params"]
    assert jax.tree.map(jnp.shape, params) == {
        "in_proj": (32, 5 * 32 + 4), "conv_kernel": (4, 96),
        "dt_bias": (32,), "A_log": (4,), "norm": (8,),
        "out_proj": {"kernel": (32, 32)}}
    params = jax.tree.map(lambda a: 8.0 * a if a.ndim == 2 else a, params)
    params["dt_bias"] = jax.random.normal(jax.random.PRNGKey(2), (32,))
    params["A_log"] = jnp.log(jnp.array([0.5, 1.0, 2.0, 4.0]))
    model = dict(num_heads=4, head_dim=8, kda_lower_bound=-5.0, norm_eps=1e-6)

    def program(p, u):
        return jnp.sum(jnp.sin(mixer.apply({"params": p}, u)))

    def plain(p, u):
        return jnp.sum(jnp.sin(jax.vmap(
            lambda row: reference.kda(row, p, model))(u)))

    got, got_g = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(
        params, u)
    want, want_g = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(
        params, u)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        assert _rel(g, w) < 1e-4, jax.tree_util.keystr(path)


def test_the_convolution_is_causal_and_its_rule_is_autodiffs():
    """The mixer's convolution (``ops/kda_conv.py``, with its SiLU and
    norms) against the written-out taps, answer and both gradients (its
    backward is a kernel of its own), and position ``t`` reads ``t - 3 ..
    t`` alone."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 19, 3 * 16 + 5))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 3 * 16))
    probe = jax.random.normal(jax.random.PRNGKey(2), (2, 19, 3 * 16))

    def unit(a):
        return a * jax.lax.rsqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + ling3.L2_EPS)

    def plain(x, taps):
        s = jax.nn.silu(sum(
            jnp.pad(x[..., :48], ((0, 0), (3 - j, 0), (0, 0)))[:, :19]
            * taps[j] for j in range(4)))
        q, k, v = (a.reshape(2, 19, 2, 8) for a in jnp.split(s, 3, -1))
        return jnp.concatenate([(unit(q) * 8 ** -0.5).reshape(2, 19, 16),
                                unit(k).reshape(2, 19, 16),
                                v.reshape(2, 19, 16)], axis=-1)

    def conv(x, taps):
        return jnp.concatenate(conv_qkv(x, taps, heads=2, head_dim=8,
                                        eps=ling3.L2_EPS,
                                        dtype=jnp.float32)[:3], axis=-1)

    np.testing.assert_array_equal(conv_qkv(
        x, taps, heads=2, head_dim=8, eps=ling3.L2_EPS,
        dtype=jnp.float32)[3], x[..., 48:])
    np.testing.assert_allclose(conv(x, taps), plain(x, taps),
                               rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(conv(*a) * probe),
                   argnums=(0, 1))(x, taps)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * probe),
                    argnums=(0, 1))(x, taps)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    moved = conv(x.at[:, 10].add(1.0), taps) - conv(x, taps)
    assert not np.asarray(moved[:, :10]).any()
    assert not np.asarray(moved[:, 14:]).any()
    assert np.asarray(moved[:, 10:14]).all()


def test_the_decays_differ_by_channel_and_stay_above_the_bound():
    """What the mixer hands the rule: unit keys, queries of norm d^-1/2,
    log-decays in (-5, 0) that differ within a head, steps in (0, 1)."""
    seen = {}

    def spy(q, k, v, g, beta, *, chunk):
        seen.update(q=q, k=k, g=g, beta=beta, chunk=chunk)
        return kda.kda_chunked(q, k, v, g, beta, chunk=chunk)

    u = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    mixer = ling3.KdaMixer(num_heads=4, head_dim=8, conv_kernel=4, chunk=8,
                           lower_bound=-5.0)
    params = mixer.init(jax.random.PRNGKey(1), u)["params"]
    params["A_log"] = jnp.zeros((4,))
    params["dt_bias"] = jnp.zeros((32,))
    params["in_proj"] = 10 * params["in_proj"]
    original, ling3.kda_chunked = ling3.kda_chunked, spy
    try:
        mixer.apply({"params": params}, u)
    finally:
        ling3.kda_chunked = original
    np.testing.assert_allclose(
        jnp.linalg.norm(seen["k"], axis=-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(
        jnp.linalg.norm(seen["q"], axis=-1), 8 ** -0.5, atol=1e-4)
    g = np.asarray(seen["g"])
    assert g.shape == (1, 16, 4, 8) and -5.0 < g.min() < g.max() < 0.0
    assert float(np.std(g, axis=-1).min()) > 1e-3
    assert 0.0 < float(seen["beta"].min()) and float(seen["beta"].max()) < 1.0
    assert seen["chunk"] == 8 and _snapshot()["kda.chunk"] == 8


def test_latent_attention_without_a_query_rank_on_both_cores():
    """No query rank, a norm a head on q and k before the rotation, a gate
    a head: the flash kernel and the written-out scores agree, and the
    parameters say what was built."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    outs = {}
    for impl in mla.MLA_IMPLS:
        layer = mla.LatentAttention(
            num_heads=4, q_rank=0, kv_rank=8, nope_dim=8, rope_dim=4,
            v_dim=8, yarn=(1.0, 0, 0.0, 0.0, 0.0), rope_theta=6e6, impl=impl,
            init_std=0.3, qk_norm=True, head_gate=True)
        params = layer.init(jax.random.PRNGKey(1), u)["params"]
        assert jax.tree.map(jnp.shape, params) == {
            "q": {"kernel": (32, 48)}, "kv_a": {"kernel": (32, 12)},
            "kv_norm": (8,), "kv_b": {"kernel": (8, 64)},
            "q_head_norm": (12,), "k_head_norm": (12,),
            "gate": {"kernel": (32, 4)}, "out": {"kernel": (32, 32)}}
        params["q_head_norm"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(2), (12,))
        outs[impl] = layer.apply({"params": params}, u)
    np.testing.assert_allclose(outs["flash"], outs["dense"], atol=1e-5)
    model = dict(num_heads=4, nope_dim=8, rope_dim=4, v_dim=8, kv_rank=8,
                 norm_eps=1e-6, rope_theta=6e6)
    want = jax.vmap(lambda row: reference.mla(row, params, model))(u)
    np.testing.assert_allclose(outs["dense"], want, atol=2e-5)


# --- the clamp ----------------------------------------------------------------


@pytest.mark.parametrize("expert_limit,shared_limit",
                         [(0.3, 0.0), (0.0, 0.2), (0.3, 0.2)])
def test_the_clamp_is_the_references(expert_limit, shared_limit):
    """A layer's limits clip the gate's pre-activation above and the
    up-projection on both sides, in the routed experts and in the shared
    one apart; answer and every gradient leaf against the reference, and
    the clamp bites (the unclamped layer answers otherwise)."""
    layer = moe.GatedMoEShare(
        embed_dim=32, expert_dim=24, shared_dim=24, experts_total=16,
        experts_held=(4, 4), top_k=4, routed_scale=2.5, init_std=0.5,
        row_tile=16, n_group=4, topk_group=2, expert_limit=expert_limit,
        shared_limit=shared_limit)
    u = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    # Send the tokens to the held experts' group, so that the banks answer.
    params["router_bias"] = params["router_bias"].at[4:8].set(10.0)
    model = dict(experts_per_token=4, routed_scale=2.5, experts_first=4,
                 expert_groups=4, expert_groups_kept=2,
                 expert_limits=(expert_limit,),
                 shared_expert_limits=(shared_limit,))

    def program(p, u):
        return jnp.sum(jnp.sin(layer.apply({"params": p}, u)))

    def plain(p, u):
        return jnp.sum(jnp.sin(reference.moe(u, p, model, 0)))

    got, got_g = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(
        params, u)
    want, want_g = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(
        params, u)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-4)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(g).any()
        else:
            assert _rel(g, w) < 2e-5, name
    free = dataclasses.replace(layer, expert_limit=0.0, shared_limit=0.0)
    assert _rel(free.apply({"params": params}, u),
                layer.apply({"params": params}, u)) > 0.05


def test_a_gated_block_clips_gate_above_and_up_on_both_sides():
    u = jnp.eye(3)
    w_gate = jnp.diag(jnp.array([2.0, -2.0, 0.1]))
    w_up = jnp.diag(jnp.array([3.0, -3.0, 0.1]))
    got = moe.gated(u, w_gate, w_up, jnp.eye(3), limit=0.5)
    silu = jax.nn.silu
    np.testing.assert_allclose(jnp.diag(got), jnp.array([
        silu(0.5) * 0.5, silu(-2.0) * -0.5, silu(0.1) * 0.1]), rtol=1e-6)
    np.testing.assert_allclose(
        jnp.diag(moe.gated(u, w_gate, w_up, jnp.eye(3))),
        jnp.array([silu(2.0) * 3.0, silu(-2.0) * -3.0, silu(0.1) * 0.1]),
        rtol=1e-6)


# --- the model ----------------------------------------------------------------


@pytest.mark.parametrize("impl,remat,limits", [
    ("dense", False, {}), ("flash", True, {}), ("flash", True, LIMITS)],
    ids=["dense", "flash_remat", "flash_remat_clamped"])
def test_model_matches_the_plain_reference(impl, remat, limits):
    """The loss (to 1e-5) and every gradient leaf (to 1e-3), float32
    against float32, with both attention cores, rematerialised and not,
    with and without clamps."""
    model, params, ids, y, config = _model_and_batch(
        attn_impl=impl, remat=remat, **limits)

    def program(p):
        return losses.softmax_cross_entropy(
            model.apply({"params": p}, ids, train=True), y)

    loss, grads = jax.jit(jax.value_and_grad(program))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, ids, y, config)))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for (path, want), got in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree.leaves(grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(got).any(), name
        else:
            assert _rel(got, want) < 1e-3, name


def test_remat_on_and_off_alike():
    """The same loss and gradients, and a rematerialised layer keeps the
    rule's three names beside the share layer's nine."""
    answers = []
    for remat in (False, True):
        model, params, ids, y, _ = _model_and_batch(remat=remat)
        answers.append(jax.jit(jax.value_and_grad(
            lambda p: losses.softmax_cross_entropy(
                model.apply({"params": p}, ids, train=True), y)))(params))
        got = _snapshot()
        assert got["kda.remat_saved_arrays"] == (
            len(kda.KDA_RESIDUAL_NAMES) if remat else 0)
        assert got["moe.remat_saved_arrays"] == (
            len(moe.SHARE_RESIDUAL_NAMES) if remat else 0)
    (loss, grads), (loss_r, grads_r) = answers
    np.testing.assert_allclose(loss, loss_r, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_r)):
        assert not np.asarray(b).any() or _rel(a, b) < 1e-3


def test_a_rematerialised_layer_runs_each_rule_kernel_once():
    """With ``remat`` the gradient holds one forward and one backward rule
    kernel a delta-rule layer (six of seven here): a layer keeps the rule's
    states, pseudo-values and output, so what it makes again runs none."""
    model, params, ids, y, _ = _model_and_batch(remat=True)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: losses.softmax_cross_entropy(
            model.apply({"params": p}, ids, train=True), y)))(params))
    kernels = re.findall(r"\bname=(kda_(?:fwd|bwd))\b", text)
    assert kernels.count("kda_fwd") == kernels.count("kda_bwd") == 6


def test_a_rematerialised_layer_runs_the_convolution_forward_twice():
    """With ``remat`` the gradient holds, a delta-rule layer, the fused
    convolution's forward kernel in the forward pass and again where the
    layer is made again (the rule reads q, k and v, which are not kept),
    and one backward kernel."""
    model, params, ids, y, _ = _model_and_batch(remat=True)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: losses.softmax_cross_entropy(
            model.apply({"params": p}, ids, train=True), y)))(params))
    kernels = re.findall(r"\bname=(kda_conv_(?:fwd|bwd))\b", text)
    assert kernels.count("kda_conv_fwd") == 12
    assert kernels.count("kda_conv_bwd") == 6


def test_model_logits_and_the_references():
    model, params, ids, _, config = _model_and_batch(**LIMITS)
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 64, 96) and logits.dtype == jnp.float32
    assert _rel(logits, reference.forward(params, ids, config)) < 1e-5


@pytest.mark.parametrize("position", [1, 17, 40])
def test_model_is_causal(position):
    model, params, ids, _, _ = _model_and_batch()
    before = model.apply({"params": params}, ids)
    changed = ids.at[:, position].set((ids[:, position] + 1) % 96)
    after = model.apply({"params": params}, changed)
    # To rounding: the share layer adds a token's rows in the order of the
    # block's routing, which the changed token moves.
    np.testing.assert_allclose(before[:, :position], after[:, :position],
                               atol=1e-5)
    assert not np.allclose(before[:, position:], after[:, position:])


def test_gauges_say_what_was_built():
    _model_and_batch()
    got = _snapshot()
    assert (got["kda.layers"], got["kda.heads"], got["kda.chunk"]) == (6, 4, 8)
    assert (got["moe.groups"], got["moe.groups_kept"]) == (4, 2)
    assert (got["moe.experts_held"], got["moe.experts_total"],
            got["moe.top_k"], got["moe.row_tile"]) == (4, 16, 4, 16)
    assert (got["mla.heads"], got["mla.qk_dim"], got["mla.v_dim"],
            got["mla.kv_rank"]) == (4, 12, 8, 8)
    from colearn_federated_learning_tpu.analysis import metric_catalog
    for name in ("kda.layers", "kda.heads", "kda.chunk",
                 "kda.remat_saved_arrays", "moe.groups", "moe.groups_kept",
                 "ling3.layers"):
        assert name in metric_catalog.GAUGES, name
    for name in ("ops.kda_trace_total", "ops.kda_conv_trace_total"):
        assert name in metric_catalog.COUNTERS, name
    # Rematerialised, a layer keeps the rule's states, pseudo-values and
    # output; off the TPU the rule's and the convolution's kernels were
    # interpreted, never built for Mosaic (counted before and after the
    # build: a test of another file in this process may have compiled them
    # for a described chip).
    counts = [f"{name}{{mode={mode}}}"
              for name in ("ops.kda_trace_total", "ops.kda_conv_trace_total")
              for mode in ("interpret", "mosaic")]
    before = {key: got.get(key, 0) for key in counts}
    _model_and_batch(remat=True)
    got = _snapshot()
    assert got["kda.remat_saved_arrays"] == 3 == len(kda.KDA_RESIDUAL_NAMES)
    for key, count in before.items():
        if "interpret" in key:
            assert got[key] > count, key
        else:
            assert got.get(key, 0) == count, key


def test_registry_guards_name_the_family():
    with pytest.raises(ValueError, match="leading dense layers"):
        _model_and_batch(dense_layers=8)
    with pytest.raises(ValueError, match="not 'ring'"):
        registry.build_model(ModelConfig(**{**TINY, "attn_impl": "ring"}))
    with pytest.raises(ValueError, match="not 'ling3'"):
        registry.build_model(ModelConfig(**TINY), seq_axis_name="seq")
    with pytest.raises(ValueError, match="clamps for 7 layers"):
        _model_and_batch(expert_limits=(1.0, 2.0))
    with pytest.raises(ValueError, match="within 3 of 4 groups"):
        _model_and_batch(expert_groups_kept=3, experts_per_token=13)
    shipped = get_config("ling3_fedavg").model
    assert shipped.remat and (shipped.width, shipped.depth) == (2560, 7)
    built = registry.build_model(shipped)
    assert built.experts_held == (0, 8)
    assert (built.token_block, built.row_tile) == (2048, 1024)
    assert (built.n_group, built.topk_group, built.first_layer) == (8, 4, 1)


def _experiment(**model):
    shipped = get_config("ling3_fedavg")
    return ExperimentConfig(
        data=DataConfig(dataset="tokens_tiny", num_clients=4,
                        partition="iid"),
        model=dataclasses.replace(
            shipped.model, **{**TINY, "dtype": "float32", **model}),
        fed=dataclasses.replace(shipped.fed, cohort_size=1, lr=0.1),
        run=RunConfig(name="ling3_tiny", eval_every=1))


def test_fit_trains_and_evaluates():
    """Two rounds through ``FederatedLearner.from_config`` with an
    evaluation after each: every record is evaluated per token, the loss
    falls, one round program was built."""
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
    assert _snapshot()["kda.layers"] == 6
