"""Ulysses (all-to-all) sequence parallelism (parallel/ulysses.py).

The second long-context layout next to ring attention: one all-to-all to
head-sharding, local dense attention over the full sequence, one
all-to-all back.  Must match the dense oracle exactly and train through
the federated 2-D (clients, seq) mesh like the ring path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.parallel.mesh import make_mesh
from colearn_federated_learning_tpu.parallel.ring import dense_attention
from colearn_federated_learning_tpu.parallel.ulysses import ulysses_attention
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def _run_sharded(fn, mesh, args, specs, out_spec):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                 out_specs=out_spec, check_vma=False))(*args)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense_oracle(cpu_devices, causal):
    mesh = Mesh(np.array(cpu_devices[:4]), ("seq",))
    B, L, H, D = 2, 32, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, L, H, D), jnp.float32) for kk in ks)
    mask = jax.random.bernoulli(jax.random.PRNGKey(3), 0.8, (B, L))

    ref = dense_attention(q, k, v, mask, causal=causal)
    out = _run_sharded(
        lambda q_, k_, v_, m_: ulysses_attention(
            q_, k_, v_, m_, axis_name="seq", causal=causal
        ),
        mesh, (q, k, v, mask),
        (P(None, "seq"), P(None, "seq"), P(None, "seq"), P(None, "seq")),
        P(None, "seq"),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_rejects_indivisible_heads(cpu_devices):
    mesh = Mesh(np.array(cpu_devices[:4]), ("seq",))
    q = jnp.zeros((1, 16, 3, 8))         # 3 heads / 4-way axis
    with pytest.raises(ValueError, match="divisible"):
        _run_sharded(
            lambda x: ulysses_attention(x, x, x, axis_name="seq"),
            mesh, (q,), (P(None, "seq"),), P(None, "seq"),
        )


def test_federated_ulysses_matches_single_device(cpu_devices):
    model = dict(name="bert", num_classes=4, width=16, depth=1, num_heads=4,
                 seq_len=64, vocab_size=2000)
    base = ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=4, partition="iid",
                        max_examples_per_client=8),
        model=ModelConfig(**model),
        fed=FedConfig(strategy="fedavg", rounds=1, cohort_size=0,
                      local_steps=2, batch_size=4, lr=0.05, momentum=0.9),
        run=RunConfig(name="ulysses_fed"),
    )
    cfg = base.replace(model=ModelConfig(**{**model, "attn_impl": "ulysses"}))
    mesh = make_mesh(("clients", "seq"), (4, 2), devices=cpu_devices[:8])
    sp = FederatedLearner(cfg, mesh=mesh)
    assert sp.sp
    ref = FederatedLearner(base)
    for _ in range(2):
        r_sp = sp.run_round()
        r_ref = ref.run_round()
    np.testing.assert_allclose(r_sp["train_loss"], r_ref["train_loss"],
                               rtol=1e-5)
    p1 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(sp.server_state.params)])
    p2 = np.concatenate([np.ravel(np.asarray(a))
                         for a in jax.tree.leaves(ref.server_state.params)])
    np.testing.assert_allclose(p1, p2, atol=2e-6)
