"""Tensor parallelism (parallel/tp.py): partition rules + engine integration.

The reference has no model parallelism (SURVEY.md §2: TP/PP/SP/EP absent);
these tests cover the rebuild's TP superset: GSPMD-auto ``model`` axis
composed with the manual ``clients`` shard_map axis, numerically equivalent
to the single-device vmap path.
"""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.models import registry as model_registry
from colearn_federated_learning_tpu.parallel import tp as tp_lib
from colearn_federated_learning_tpu.parallel.mesh import make_mesh
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def _bert_cfg(**fed_kw):
    fed = dict(strategy="fedavg", rounds=1, cohort_size=0, local_steps=2,
               batch_size=4, lr=0.05, momentum=0.9)
    fed.update(fed_kw)
    return ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=8, partition="iid",
                        max_examples_per_client=8),
        model=ModelConfig(name="bert", num_classes=4, width=32, depth=1,
                          num_heads=4, seq_len=64, vocab_size=2000),
        fed=FedConfig(**fed),
        run=RunConfig(name="tp_test"),
    )


def _tiny_params(name, **kw):
    import jax.numpy as jnp

    cfg = ModelConfig(name=name, num_classes=4, width=32, depth=1,
                      num_heads=4, seq_len=64, vocab_size=2000, **kw)
    model = model_registry.build_model(cfg)
    x = (jnp.zeros((2, 64), jnp.int32) if name == "bert"
         else jnp.zeros((2, 28, 28, 1), jnp.float32))
    return model_registry.init_params(model, x, jax.random.PRNGKey(0))


def test_param_specs_bert_rules():
    params = _tiny_params("bert")
    specs = tp_lib.param_specs(params, "model", 2)
    blk = specs["TransformerBlock_0"]
    attn = blk["MultiHeadAttention_0"]
    # (D, H, hd) q/k/v kernels: heads dim sharded; (H, hd) bias: dim 0.
    assert attn["query"]["kernel"] == P(None, "model", None)
    assert attn["query"]["bias"] == P("model", None)
    # (H, hd, D) out projection: row parallel, bias replicated.
    assert attn["out"]["kernel"] == P("model", None, None)
    assert attn["out"]["bias"] == P()
    # Block MLP: up column-parallel, down row-parallel.
    assert blk["Dense_0"]["kernel"] == P(None, "model")
    assert blk["Dense_0"]["bias"] == P("model")
    assert blk["Dense_1"]["kernel"] == P("model", None)
    assert blk["Dense_1"]["bias"] == P()
    # Token embedding table vocab-sharded; norms replicated.
    assert specs["Embed_0"]["embedding"] == P("model", None)
    assert specs["LayerNorm_0"]["scale"] == P()
    assert tp_lib.sharded_fraction(params, "model", 2) > 0.5


def test_param_specs_vit_rules():
    params = _tiny_params("vit_b16", patch_size=4)
    specs = tp_lib.param_specs(params, "model", 2)
    blk = specs["ViTBlock_0"]
    assert blk["MultiHeadAttention_0"]["query"]["kernel"] == P(None, "model", None)
    assert blk["Dense_0"]["kernel"] == P(None, "model")
    assert specs["Conv_0"]["kernel"] == P()


def test_indivisible_dims_replicate():
    params = _tiny_params("bert")
    # 4 heads / 3-way axis does not divide: every spec must be replicated
    # rather than letting GSPMD pad.
    specs = tp_lib.param_specs(params, "model", 3)
    q = specs["TransformerBlock_0"]["MultiHeadAttention_0"]["query"]["kernel"]
    assert q == P()
    # MLP hidden 128 divides by 3? no → replicated too.
    assert specs["TransformerBlock_0"]["Dense_0"]["kernel"] == P()


def test_tp_round_matches_vmap(cpu_devices):
    cfg = _bert_cfg()
    mesh = make_mesh(("clients", "model"), (4, 2), devices=cpu_devices[:8])
    tp_learner = FederatedLearner(cfg, mesh=mesh)
    assert tp_learner.tp_size == 2
    ref = FederatedLearner(cfg)

    for _ in range(2):
        m_tp = tp_learner.run_round()
        m_ref = ref.run_round()
    assert m_tp["completed"] == m_ref["completed"] == 8
    np.testing.assert_allclose(m_tp["train_loss"], m_ref["train_loss"],
                               rtol=1e-4)
    # The second round reused the first round's executable: the state
    # came back placed as it went in.
    assert tp_learner._round_fn.compiles == 1 and "recompiles" not in m_tp

    # Params: TP-sharded leaves are genuinely distributed ...
    q = tp_learner.server_state.params["TransformerBlock_0"][
        "MultiHeadAttention_0"]["query"]["kernel"]
    assert "model" in jax.tree.leaves(tuple(q.sharding.spec))
    shard_shape = q.addressable_shards[0].data.shape
    assert shard_shape[1] == q.shape[1] // 2
    # ... and the trained model matches the single-device trajectory.
    p_tp = np.concatenate(
        [np.ravel(np.asarray(a))
         for a in jax.tree.leaves(tp_learner.server_state.params)]
    )
    p_ref = np.concatenate(
        [np.ravel(np.asarray(a))
         for a in jax.tree.leaves(ref.server_state.params)]
    )
    np.testing.assert_allclose(p_tp, p_ref, atol=2e-6)

    # Eval runs with TP-sharded params and agrees too.
    lt, at = tp_learner.evaluate()
    lr_, ar_ = ref.evaluate()
    assert abs(lt - lr_) < 1e-4 and abs(at - ar_) < 1e-6


def test_tp_composes_with_privacy(cpu_devices):
    # DP clip+noise and secure-agg masks run per-client INSIDE the manual
    # clients axis while params stay TP-sharded — the composition the
    # flagship (cross-silo ViT + DP) config needs.
    cfg = _bert_cfg(dp_clip=1.0, dp_noise_multiplier=0.1, secure_agg=True)
    mesh = make_mesh(("clients", "model"), (4, 2), devices=cpu_devices[:8])
    learner = FederatedLearner(cfg, mesh=mesh)
    m = learner.run_round()
    assert m["completed"] == 8
    assert np.isfinite(m["train_loss"])


def test_dp_sp_tp_composition(cpu_devices):
    # The full 3-D mesh: manual clients (FedAvg psum) x manual seq (ring
    # attention) x auto model (TP) — one jit program, same trajectory as
    # the single-device vmap path.
    model = ModelConfig(name="bert", num_classes=4, width=16, depth=1,
                        num_heads=2, seq_len=64, vocab_size=2000)
    base = ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=4, partition="iid",
                        max_examples_per_client=8),
        model=model,
        fed=FedConfig(strategy="fedavg", rounds=1, cohort_size=0,
                      local_steps=1, batch_size=4, lr=0.05, momentum=0.9),
        run=RunConfig(name="dp_sp_tp"),
    )
    cfg3d = base.replace(
        model=ModelConfig(**{**model.__dict__, "attn_impl": "ring"})
    )
    mesh = make_mesh(("clients", "seq", "model"), (2, 2, 2),
                     devices=cpu_devices[:8])
    learner = FederatedLearner(cfg3d, mesh=mesh)
    assert learner.sp and learner.tp_size == 2
    m = learner.run_round()
    ref = FederatedLearner(base)
    m_ref = ref.run_round()
    np.testing.assert_allclose(m["train_loss"], m_ref["train_loss"], rtol=1e-5)
    p1 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(learner.server_state.params)])
    p2 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(ref.server_state.params)])
    np.testing.assert_allclose(p1, p2, atol=2e-6)


def test_from_config_builds_tp_mesh(cpu_devices):
    cfg = _bert_cfg()
    cfg = cfg.replace(run=RunConfig(name="tp_auto", tp_size=2))
    learner = FederatedLearner.from_config(cfg)
    assert learner.mesh is not None
    assert learner.mesh.shape["model"] == 2
    assert learner.mesh.shape["clients"] == len(jax.devices()) // 2


def test_tp_checkpoint_roundtrip(cpu_devices, tmp_path):
    # Checkpoint/resume with TP-sharded server state: the restore targets
    # the LIVE sharded arrays, so shardings must survive the roundtrip.
    import dataclasses

    cfg = _bert_cfg()
    cfg = cfg.replace(run=dataclasses.replace(
        cfg.run, name="tp_ckpt", checkpoint_dir=str(tmp_path / "ck"),
        checkpoint_every=1))
    mesh = make_mesh(("clients", "model"), (4, 2), devices=cpu_devices[:8])
    a = FederatedLearner(cfg, mesh=mesh)
    a.fit(rounds=2)
    p_before = np.concatenate([np.ravel(np.asarray(x))
                               for x in jax.tree.leaves(a.server_state.params)])

    b = FederatedLearner(cfg, mesh=mesh)
    step = b.restore_checkpoint()
    assert step == 2
    q = b.server_state.params["TransformerBlock_0"][
        "MultiHeadAttention_0"]["query"]["kernel"]
    assert q.addressable_shards[0].data.shape[1] == q.shape[1] // 2
    p_after = np.concatenate([np.ravel(np.asarray(x))
                              for x in jax.tree.leaves(b.server_state.params)])
    np.testing.assert_array_equal(p_before, p_after)
