"""Mixture-of-Experts family (models/moe.py) + expert parallelism.

EP is absent in the reference (SURVEY.md §2); this is the rebuild's
distributed superset: capacity-based static-shape routing, Switch aux loss
via sow, expert banks sharded over the ``model`` axis (parallel/tp.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.models import registry as model_registry
from colearn_federated_learning_tpu.models.moe import MoEFfn
from colearn_federated_learning_tpu.parallel import tp as tp_lib
from colearn_federated_learning_tpu.parallel.mesh import make_mesh
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def _moe_cfg(**model_kw):
    model = dict(name="moe_bert", num_classes=4, width=32, depth=1,
                 num_heads=4, seq_len=64, vocab_size=2000, num_experts=4)
    model.update(model_kw)
    return ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=8, partition="iid",
                        max_examples_per_client=16),
        model=ModelConfig(**model),
        fed=FedConfig(strategy="fedavg", rounds=3, cohort_size=0,
                      local_steps=2, batch_size=4, lr=0.05, momentum=0.9),
        run=RunConfig(name="moe_test"),
    )


def test_moe_forward_shape_and_aux():
    cfg = _moe_cfg()
    model = model_registry.build_model(cfg.model)
    x = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 1, 2000)
    params = model_registry.init_params(model, x, jax.random.PRNGKey(0))
    logits = model.apply({"params": params}, x, train=False)
    assert logits.shape == (4, 4)
    assert bool(jnp.isfinite(logits).all())

    # Training-mode apply sows one Switch aux value per MoE layer; at init
    # the router is near-uniform so the aux sits near its optimum 1.0.
    _, upd = model.apply({"params": params}, x, train=True,
                         mutable=["intermediates"])
    leaves = [
        v for p, v in jax.tree_util.tree_leaves_with_path(upd["intermediates"])
        if any(getattr(q, "key", None) == "moe_aux" for q in p)
    ]
    # GShard interleaving: depth//2 MoE blocks, except depth==1 -> 1.
    d = cfg.model.depth
    assert len(leaves) == (1 if d == 1 else d // 2)
    assert 0.9 < float(leaves[0]) < 1.5


def test_moe_capacity_limits_tokens():
    # With a tiny capacity factor most tokens are dropped (block output
    # shrinks toward zero); ample capacity routes everything.
    D, E = 16, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, D))
    tight = MoEFfn(embed_dim=D, num_experts=E, capacity_factor=0.05)
    ample = MoEFfn(embed_dim=D, num_experts=E, capacity_factor=4.0)
    pt = tight.init(jax.random.PRNGKey(1), x)["params"]
    out_t = tight.apply({"params": pt}, x)
    out_a = ample.apply({"params": pt}, x)
    assert bool(jnp.isfinite(out_t).all()) and bool(jnp.isfinite(out_a).all())
    # Tight capacity must carry strictly less routed mass.
    assert float(jnp.abs(out_t).sum()) < 0.5 * float(jnp.abs(out_a).sum())


def test_moe_padding_tokens_excluded():
    # Padding tokens must claim no expert capacity: with exactly enough
    # capacity for the real tokens, every real token still routes (nonzero
    # output) and every pad position contributes zero.
    D, E = 16, 2
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, D))
    mask = jnp.arange(32)[None, :] < 16                  # half the row real
    layer = MoEFfn(embed_dim=D, num_experts=E, top_k=1, capacity_factor=1.0)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    out = layer.apply({"params": params}, x, token_mask=mask)
    pad_out = out[0, 16:]
    assert float(jnp.abs(pad_out).max()) == 0.0
    # capacity C = N/E = 16 per expert >= 16 real tokens: none dropped even
    # if the router sends every real token to one expert.
    real_rows = jnp.abs(out[0, :16]).max(axis=-1)
    assert float(real_rows.min()) > 0.0
    # Aux statistics ignore pads: a uniform-ish router over real tokens
    # keeps the Switch loss near 1.
    _, upd = layer.apply({"params": params}, x, token_mask=mask,
                         mutable=["intermediates"])
    (aux,) = [
        v for p, v in jax.tree_util.tree_leaves_with_path(upd["intermediates"])
        if any(getattr(q, "key", None) == "moe_aux" for q in p)
    ]
    assert 0.5 < float(aux) < 2.0


def test_moe_trains_and_balances():
    learner = FederatedLearner(_moe_cfg())
    hist = learner.fit(rounds=3)
    assert hist[-1]["train_loss"] < hist[0]["train_loss"]
    assert np.isfinite(learner.evaluate()[0])


def test_moe_expert_parallel_matches_single_device(cpu_devices):
    cfg = _moe_cfg()
    ref = FederatedLearner(cfg)
    for _ in range(2):
        ref.run_round()

    mesh = make_mesh(("clients", "model"), (4, 2), devices=cpu_devices[:8])
    ep = FederatedLearner(cfg, mesh=mesh)
    assert tp_lib.sharded_fraction(ep.params, "model", 2) > 0.8
    # Expert banks are genuinely distributed over the model axis.
    bank = ep.params["TransformerBlock_0"]["MoEFfn_0"]["experts_up"]
    assert bank.addressable_shards[0].data.shape[0] == bank.shape[0] // 2
    for _ in range(2):
        m = ep.run_round()
    assert np.isfinite(m["train_loss"])
    # One executable for both rounds: the partitioner is not left free to
    # move the replicated router weights onto the model axis on the way out.
    assert ep._round_fn.compiles == 1 and "recompiles" not in m

    p1 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(ep.server_state.params)])
    p2 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(ref.server_state.params)])
    np.testing.assert_allclose(p1, p2, atol=2e-6)


# --- the share layer's group-limited choice (models/moe.py ExpertShare) -----


def _gated_share(total, groups, kept, top_k, first=0, count=4, **kw):
    from colearn_federated_learning_tpu.models.moe import GatedMoEShare

    return GatedMoEShare(
        embed_dim=32, expert_dim=24, shared_dim=40, experts_total=total,
        experts_held=(first, count), top_k=top_k, routed_scale=2.5,
        init_std=0.3, row_tile=16, n_group=groups, topk_group=kept, **kw)


def _route_model(total, groups, kept, top_k, first=0):
    return dict(experts_per_token=top_k, routed_scale=2.5,
                experts_first=first, expert_groups=groups,
                expert_groups_kept=kept)


@pytest.mark.parametrize("total,groups,kept,top_k", [
    (16, 4, 2, 4), (64, 8, 4, 8), (64, 8, 1, 8), (24, 3, 3, 5)])
@pytest.mark.parametrize("biased", [False, True], ids=["no_bias", "bias"])
def test_group_limited_choice_is_the_sorted_one(total, groups, kept, top_k,
                                                biased):
    """``route`` against the reference's choice by sorting: a group's score
    is the sum of its two largest ``s + b``, the best groups are kept, the
    experts are the largest ``s + b`` within them; the weights are of the
    scores without the bias.  Every choice lies in a kept group, and with
    every group kept the choice is the plain one."""
    from benchmarks.reference import ling3 as reference

    layer = _gated_share(total, groups, kept, top_k)
    u = jax.random.normal(jax.random.PRNGKey(0), (96, 32))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    if biased:
        params["router_bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(2), (total,))
    chosen, weights = layer.apply({"params": params}, u, method="route")
    want, want_w = reference.route(
        u, params, _route_model(total, groups, kept, top_k))
    order, want_order = np.argsort(chosen, -1), np.argsort(want, -1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(chosen), order, -1),
        np.take_along_axis(np.asarray(want), want_order, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), order, -1),
        np.take_along_axis(np.asarray(want_w), want_order, -1), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    per_group = total // groups
    assert (np.array([len(set(row)) for row in np.asarray(chosen)
                      // per_group]) <= kept).all()
    if kept == groups:
        plain = _gated_share(total, 1, 1, top_k).apply(
            {"params": params}, u, method="route")[0]
        np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(plain, -1))
    else:
        # The limit binds: the plain choice would have left the groups.
        plain = _gated_share(total, 1, 1, top_k).apply(
            {"params": params}, u, method="route")[0]
        assert (np.sort(chosen, -1) != np.sort(plain, -1)).any()


def test_one_group_routes_as_before():
    """``n_group`` 1 passes the scores on as they came: the lowered router
    holds the one ``top_k`` it held, and the grouped one three."""
    u = jax.random.normal(jax.random.PRNGKey(0), (64, 32))

    def lowered(layer):
        params = layer.init(jax.random.PRNGKey(1), u)["params"]
        return jax.jit(lambda p, u: layer.apply(
            {"params": p}, u, method="route")).lower(params, u).as_text()

    plain, stated, grouped = (lowered(_gated_share(16, *groups, 4))
                              for groups in ((1, 1), (1, 1), (4, 2)))
    assert plain == stated
    assert plain.count("top_k") < grouped.count("top_k")
    assert "0xFF800000" not in plain and "0xFF800000" in grouped   # -inf
    layer = _gated_share(16, 1, 1, 4)
    scores = jnp.arange(32.0).reshape(2, 16)
    assert layer.within_kept_groups(scores) is scores


@pytest.mark.parametrize("total,groups,kept,top_k,count", [
    (64, 8, 4, 8, 4), (16, 4, 2, 4, 4)], ids=["16_shares", "4_shares"])
def test_the_shares_add_up_under_the_group_limited_choice(total, groups, kept,
                                                          top_k, count):
    """The program's layer, built as one chip's share and given that
    share's slice of an uncut layer's banks, once for each share (in the
    first case a group spans two shares, as the deployment's spans eight
    chips): the routed parts summed, with what every chip computes alike
    (the router, the shared expert) counted once, are the uncut reference's
    layer, and every (token, choice) pair lies on exactly one share."""
    from benchmarks.reference import ling3 as reference
    from colearn_federated_learning_tpu.models import moe

    u = jax.random.normal(jax.random.PRNGKey(0), (48, 32))
    sizes = {"router": (32, total), "router_bias": (total,),
             "experts_gate": (total, 32, 24), "experts_up": (total, 32, 24),
             "experts_down": (total, 24, 32), "shared_gate": (32, 40),
             "shared_up": (32, 40), "shared_down": (40, 32)}
    keys = jax.random.split(jax.random.PRNGKey(1), len(sizes))
    p = {name: 0.4 * jax.random.normal(k, shape)
         for k, (name, shape) in zip(keys, sizes.items())}
    want = reference.moe(u, p, _route_model(total, groups, kept, top_k), 0)
    parts, pairs = [], 0
    for first in range(0, total, count):
        layer = _gated_share(total, groups, kept, top_k, first, count)
        own = dict(p, **{name: p[name][first:first + count] for name in (
            "experts_gate", "experts_up", "experts_down")})
        parts.append(layer.apply({"params": own}, u, u, method="routed_part"))
        chosen, weights = layer.apply({"params": own}, u, method="route")
        pairs += int(moe.held_pairs(chosen, weights, first, count)[3].sum())
    assert pairs == 48 * top_k
    shared = layer.apply({"params": own}, u, method="shared")
    got = sum(parts) + shared
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5
