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
