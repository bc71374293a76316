"""ops/attention.py flash kernel vs the dense oracle (interpret mode on CPU),
plus the pluggable MultiHeadAttention module: identical params across cores,
matching outputs, usable gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.models.attention import MultiHeadAttention
from colearn_federated_learning_tpu.ops.attention import flash_attention
from colearn_federated_learning_tpu.parallel.ring import dense_attention


def _rand(key, B, L, H, D, frac_pad=0.25):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, L, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, H, D), jnp.float32)
    mask = jax.random.uniform(ks[3], (B, L)) > frac_pad
    return q, k, v, mask


@pytest.mark.parametrize("L,block", [(32, 16), (48, 16), (40, 128)])
def test_flash_matches_dense(L, block):
    q, k, v, mask = _rand(jax.random.PRNGKey(0), B=2, L=L, H=2, D=8)
    out = flash_attention(q, k, v, mask, block_q=block, block_k=block)
    ref = dense_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_causal_and_nomask():
    q, k, v, _ = _rand(jax.random.PRNGKey(1), B=1, L=32, H=2, D=8)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_fully_masked_rows_zero():
    q, k, v, _ = _rand(jax.random.PRNGKey(2), B=2, L=16, H=1, D=4)
    mask = jnp.zeros((2, 16), bool).at[1].set(True)
    out = flash_attention(q, k, v, mask, block_q=8, block_k=8)
    assert np.allclose(np.asarray(out)[0], 0.0)
    ref = dense_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_grads_match_dense():
    q, k, v, mask = _rand(jax.random.PRNGKey(3), B=2, L=16, H=2, D=4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask, block_q=8, block_k=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, mask) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_mha_module_cores_agree():
    B, L, D, H = 2, 24, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(4), (B, L, D))
    mask = jax.random.uniform(jax.random.PRNGKey(5), (B, L)) > 0.2
    dense_m = MultiHeadAttention(num_heads=H, impl="dense")
    flash_m = MultiHeadAttention(num_heads=H, impl="flash")
    params = dense_m.init(jax.random.PRNGKey(6), x, mask)
    # Same param pytree regardless of core.
    chex_tree = jax.tree.structure(params)
    assert jax.tree.structure(flash_m.init(jax.random.PRNGKey(6), x, mask)) == chex_tree
    yd = dense_m.apply(params, x, mask)
    yf = flash_m.apply(params, x, mask)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yf),
                               rtol=1e-5, atol=1e-5)


def test_mha_module_bad_impl():
    x = jnp.zeros((1, 8, 8))
    with pytest.raises(ValueError, match="unknown attn impl"):
        MultiHeadAttention(num_heads=2, impl="nope").init(
            jax.random.PRNGKey(0), x
        )


def test_bert_model_flash_matches_dense():
    import dataclasses

    from colearn_federated_learning_tpu.models import registry
    from colearn_federated_learning_tpu.utils.config import ModelConfig

    cfg = ModelConfig(name="bert", num_classes=4, width=32, depth=2,
                      num_heads=4, seq_len=16, vocab_size=100)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 100)
    dense = registry.build_model(cfg)
    flash = registry.build_model(dataclasses.replace(cfg, attn_impl="flash"))
    params = registry.init_params(dense, ids, jax.random.PRNGKey(1))
    yd = dense.apply({"params": params}, ids, train=False)
    yf = flash.apply({"params": params}, ids, train=False)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yf),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("L,block", [(40, 16), (32, 128)])
def test_flash_backward_padded_blocks_match_dense(L, block):
    """The Pallas backward must handle block padding exactly: odd L forces
    padded q/k rows through both bwd kernels."""
    q, k, v, mask = _rand(jax.random.PRNGKey(7), B=2, L=L, H=2, D=8)

    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, mask, block_q=block, block_k=block) ** 2
        ), argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, mask) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_backward_causal_matches_dense():
    q, k, v, _ = _rand(jax.random.PRNGKey(8), B=1, L=32, H=2, D=8)

    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=8, block_k=8) ** 2
        ), argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_backward_fully_masked_rows_zero_grad():
    """Batch 0 has every key masked: its dq must be exactly zero and dk/dv
    must receive no contribution from it."""
    q, k, v, _ = _rand(jax.random.PRNGKey(9), B=2, L=16, H=1, D=4)
    mask = jnp.zeros((2, 16), bool).at[1].set(True)

    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, mask, block_q=8, block_k=8) ** 2
        ), argnums=(0, 1, 2),
    )(q, k, v)
    assert np.allclose(np.asarray(gf[0])[0], 0.0)
    assert np.allclose(np.asarray(gf[1])[0], 0.0)
    assert np.allclose(np.asarray(gf[2])[0], 0.0)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, mask) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


# --- values narrower than the scores, and a scale of the caller's --------------


def _oracle(q, k, v, mask, scale, causal, prefix):
    """The written-out scores on repeated heads: ``scale`` on them, the
    first ``prefix`` keys seen by every query, the others causally."""
    share = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, share, axis=2), jnp.repeat(v, share, axis=2)
    if not prefix:
        return dense_attention(q, k, v, mask, causal=causal, scale=scale)
    Lq, Lk = q.shape[1], k.shape[1]
    seen = (jnp.arange(Lk)[None, :] < prefix) | (
        jnp.arange(Lq)[:, None] >= jnp.arange(Lk)[None, :] - prefix)
    if mask is not None:
        seen = seen[None] & mask[:, None, :]
    else:
        seen = seen[None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    weights = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


@pytest.mark.parametrize("case", ["plain", "causal", "masked_causal",
                                  "prefix", "shared_heads"])
@pytest.mark.parametrize("widths", [(12, 8), (192, 128)],
                         ids=["12_8", "192_128"])
def test_flash_values_narrower_than_scores(widths, case):
    """Scores over ``d`` and values of ``d_v``, under an explicit ``scale``
    (latent attention's 192 / 128 with yarn's factor, and a small pair):
    forward and the three gradients against the written-out scores, with
    ``causal``, a key mask, a ``prefix`` and fewer key/value heads; the
    result and ``dv`` have the values' width."""
    d, d_v = widths
    B, L, H = 2, 24, 4
    prefix = 8 if case == "prefix" else 0
    kv_heads = 2 if case == "shared_heads" else H
    causal = case != "plain"
    scale = 2.0048 * d ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(d), 3)
    q = jax.random.normal(ks[0], (B, L, H, d))
    k = jax.random.normal(ks[1], (B, prefix + L, kv_heads, d))
    v = jax.random.normal(ks[2], (B, prefix + L, kv_heads, d_v))
    mask = None
    if case == "masked_causal":
        mask = jnp.ones((B, L), bool).at[0, 3].set(False).at[1, 10:13].set(
            False)

    def total(f):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v))), argnums=(0, 1, 2)))

    got, got_g = total(lambda q, k, v: flash_attention(
        q, k, v, mask, causal=causal, prefix=prefix, scale=scale, block_q=8,
        block_k=8))(q, k, v)
    want, want_g = total(lambda q, k, v: _oracle(
        q, k, v, mask, scale, causal, prefix))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    out = flash_attention(q, k, v, mask, causal=causal, prefix=prefix,
                          scale=scale, block_q=8, block_k=8)
    assert out.shape == (B, L, H, d_v)
    for name, g, w in zip("qkv", got_g, want_g):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_scale_defaults_to_the_queries_width():
    """No ``scale`` is ``D ** -0.5``, as it always was; keys of another
    width than the queries are refused."""
    q, k, v, mask = _rand(jax.random.PRNGKey(11), B=1, L=16, H=2, D=8)
    np.testing.assert_allclose(
        flash_attention(q, k, v, mask, block_q=8, block_k=8),
        flash_attention(q, k, v, mask, block_q=8, block_k=8, scale=8 ** -0.5),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        dense_attention(q, k, v, mask),
        dense_attention(q, k, v, mask, scale=8 ** -0.5), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="cannot score keys"):
        flash_attention(q, k[..., :4], v)
