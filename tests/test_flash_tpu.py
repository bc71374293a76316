"""Real-TPU (non-interpret) execution of the Pallas flash kernels.

The main suite runs on a virtual CPU mesh (conftest forces the platform),
where Pallas runs in interpret mode — these tests only execute when the
process actually sits on a TPU, i.e. when run OUTSIDE the suite, on the
chip machine:

    python -m pytest tests/test_flash_tpu.py -p no:cacheprovider --noconftest

They validate that the (8, 128)-tiled kernels compile and match the dense
oracle forward AND backward on hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs a real TPU (interpret-mode coverage lives in "
           "test_ops_attention.py)",
)


def _rand(key, B, L, H, D, frac_pad=0.25):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, L, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, H, D), jnp.float32)
    mask = jax.random.uniform(ks[3], (B, L)) > frac_pad
    return q, k, v, mask


def test_flash_forward_backward_on_tpu():
    from colearn_federated_learning_tpu.ops.attention import flash_attention
    from colearn_federated_learning_tpu.parallel.ring import dense_attention

    q, k, v, mask = _rand(jax.random.PRNGKey(0), B=2, L=256, H=4, D=128)

    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, mask, interpret=False)
    )(q, k, v)
    ref = dense_attention(q, k, v, mask)
    # The MXU computes f32 matmuls at DEFAULT precision (bf16 passes), so
    # kernel-vs-oracle agreement on hardware is bf16-rounding-limited.
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-3, atol=5e-3)

    gf = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, mask, interpret=False) ** 2),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, mask) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=1e-2)


def test_flash_causal_bf16_on_tpu():
    from colearn_federated_learning_tpu.ops.attention import flash_attention
    from colearn_federated_learning_tpu.parallel.ring import dense_attention

    q, k, v, _ = _rand(jax.random.PRNGKey(1), B=1, L=512, H=2, D=64)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False)
    )(q, k, v)
    ref = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_flash_long_bf16_forward_backward_on_tpu():
    """L = 2048, D = 64, bf16 with a padding mask: past
    ``FLASH_SEQ_CROSSOVER``, where the kernel is meant to be used — four
    k-blocks per row at the default 512 block, head dim below the 128
    lanes.  Errors are taken against the largest oracle entry: bf16
    operands carry 2^-8 relative rounding into every product, so
    near-zero entries have no meaningful relative error of their own."""
    from colearn_federated_learning_tpu.ops.attention import flash_attention
    from colearn_federated_learning_tpu.parallel.ring import dense_attention

    q, k, v, mask = _rand(jax.random.PRNGKey(2), B=2, L=2048, H=4, D=64)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, interpret=False)

    def dense(q, k, v):
        return dense_attention(q, k, v, mask)

    def rel_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.abs(a - b).max() / np.abs(b).max()

    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        ref = dense(*f32)
        gd = jax.grad(loss(dense), argnums=(0, 1, 2))(*f32)
    out = jax.jit(flash)(q, k, v)
    gf = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert rel_err(out, ref) < 2e-2
    for a, b in zip(gf, gd):
        assert rel_err(a, b) < 2e-2


def test_flash_values_narrower_than_scores_bf16_on_tpu():
    """Latent attention's shapes on the hardware: L = 2048, scores over 192
    (one and a half 128-lane tiles) and values of 128, causal, a scale of
    the caller's, bf16; forward and the three gradients against the
    written-out scores in float32."""
    from colearn_federated_learning_tpu.ops.attention import flash_attention
    from colearn_federated_learning_tpu.parallel.ring import dense_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 2048, 4, 192)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2048, 4, 192)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2048, 4, 128)).astype(jnp.bfloat16)
    scale = 2.0048 * 192 ** -0.5

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True, scale=scale).astype(jnp.float32) ** 2)

    def rel_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.abs(a - b).max() / np.abs(b).max()

    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        ref = dense_attention(*f32, causal=True, scale=scale)
        gd = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(*f32)
    flash = lambda q, k, v, **kw: flash_attention(  # noqa: E731
        q, k, v, interpret=False, **kw)
    out = jax.jit(lambda q, k, v: flash(q, k, v, causal=True, scale=scale))(
        q, k, v)
    gf = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    assert out.shape == (1, 2048, 4, 128)
    assert rel_err(out, ref) < 2e-2
    for a, b in zip(gf, gd):
        assert a.shape == b.shape and rel_err(a, b) < 2e-2
