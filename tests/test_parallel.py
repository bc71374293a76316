"""parallel/: mesh factoring + ring attention vs the dense oracle.

Ring attention runs under shard_map on a virtual CPU mesh (conftest forces
8 host devices) with the sequence dimension sharded; the dense single-device
attention over the unsharded arrays is the numerics oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P


from colearn_federated_learning_tpu.parallel import factor_devices, make_mesh
from colearn_federated_learning_tpu.parallel.ring import (
    dense_attention,
    ring_attention,
)


# ---------------------------------------------------------------- mesh ----
def test_factor_devices():
    assert factor_devices(8, 1) == (8,)
    assert factor_devices(8, 2) == (4, 2)
    assert factor_devices(8, 3) == (2, 2, 2)
    assert factor_devices(6, 2) == (3, 2)
    assert factor_devices(7, 2) == (1, 7)  # prime: trailing axis gets all
    assert factor_devices(1, 2) == (1, 1)


def test_make_mesh_auto_and_explicit(cpu_devices):
    m = make_mesh(("clients", "seq"), devices=cpu_devices[:8])
    assert m.shape == {"clients": 4, "seq": 2}
    m = make_mesh(("clients", "seq"), (2, 4), devices=cpu_devices[:8])
    assert m.shape == {"clients": 2, "seq": 4}
    m = make_mesh(("a", "b"), (-1, 2), devices=cpu_devices[:8])
    assert m.shape == {"a": 4, "b": 2}
    with pytest.raises(ValueError):
        make_mesh(("a",), (3,), devices=cpu_devices[:8])


# ------------------------------------------------------- ring attention ----
def _seq_mesh(devices, n):
    return Mesh(np.array(devices[:n]), ("seq",))


def _rand_qkvm(key, B, L, H, D, frac_pad=0.25):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, L, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, H, D), jnp.float32)
    mask = jax.random.uniform(ks[3], (B, L)) > frac_pad
    return q, k, v, mask


def _run_ring(mesh, q, k, v, mask, **kw):
    fn = jax.shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, m, axis_name="seq", **kw),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    return jax.jit(fn)(q, k, v, mask)


@pytest.mark.parametrize("n_dev", [4, 8])
def test_ring_matches_dense(cpu_devices, n_dev):
    mesh = _seq_mesh(cpu_devices, n_dev)
    q, k, v, mask = _rand_qkvm(jax.random.PRNGKey(0), B=2, L=32, H=2, D=8)
    out = _run_ring(mesh, q, k, v, mask)
    ref = dense_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_causal_matches_dense(cpu_devices):
    mesh = _seq_mesh(cpu_devices, 4)
    q, k, v, mask = _rand_qkvm(jax.random.PRNGKey(1), B=2, L=16, H=2, D=4,
                               frac_pad=0.0)
    out = _run_ring(mesh, q, k, v, mask, causal=True)
    ref = dense_attention(q, k, v, mask, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_no_mask(cpu_devices):
    mesh = _seq_mesh(cpu_devices, 4)
    q, k, v, _ = _rand_qkvm(jax.random.PRNGKey(2), B=1, L=16, H=1, D=4)
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    out = jax.jit(fn)(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_fully_masked_rows_are_zero(cpu_devices):
    mesh = _seq_mesh(cpu_devices, 4)
    q, k, v, _ = _rand_qkvm(jax.random.PRNGKey(3), B=2, L=16, H=2, D=4)
    mask = jnp.zeros((2, 16), bool).at[1].set(True)  # batch 0: all pad
    out = _run_ring(mesh, q, k, v, mask)
    assert np.allclose(np.asarray(out)[0], 0.0)
    ref = dense_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_bfloat16_io(cpu_devices):
    mesh = _seq_mesh(cpu_devices, 4)
    q, k, v, mask = _rand_qkvm(jax.random.PRNGKey(4), B=1, L=16, H=2, D=8)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    out = _run_ring(mesh, qb, kb, vb, mask)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.05, atol=0.05
    )
