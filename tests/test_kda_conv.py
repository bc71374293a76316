"""KDA's short convolution with its SiLU and the unit norms of q and k
(ops/kda_conv.py) against the same steps written out in ``jax.numpy`` and
autodiff: answer, both gradients, the causal zeros of the first rows, and
which body was built."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.ops import kda_conv

EPS = 1e-6


def plain(x, taps, heads, head_dim, dtype=jnp.float32):
    """The causal taps over zeros before the sequence, SiLU, the norms over
    each head of q (times ``d^-1/2``) and of k, each rounded once."""
    inner, (reach, length) = heads * head_dim, (taps.shape[0] - 1,
                                                 x.shape[1])
    padded = jnp.pad(x[..., :3 * inner], ((0, 0), (reach, 0), (0, 0)))
    s = jax.nn.silu(sum(padded[:, j:j + length] * taps[j]
                        for j in range(reach + 1)))
    q, k, v = (a.reshape(*a.shape[:2], heads, head_dim)
               for a in jnp.split(s, 3, axis=-1))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + EPS)

    q, k = unit(q) * head_dim ** -0.5, unit(k)
    return (*(a.astype(dtype).reshape(*a.shape[:2], inner)
              for a in (q, k, v)), x[..., 3 * inner:])


def fused(x, taps, heads, head_dim, dtype=jnp.float32):
    return kda_conv.conv_qkv(x, taps, heads=heads, head_dim=head_dim,
                             eps=EPS, dtype=dtype)


# (batch, length, heads, head_dim, columns after v): 27 positions are no
# multiple of a slab or of 8, one slab; 264 are three slabs of 88, so rows
# cross slabs both ways; two heads of 64 share a block of 128 lanes.
SHAPES = [(2, 27, 4, 8, 36), (1, 264, 2, 64, 20)]


def _inputs(batch, length, heads, head_dim, extra):
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    x = 3.0 * jax.random.normal(keys[0], (batch, length,
                                          3 * heads * head_dim + extra))
    taps = jax.random.normal(keys[1], (4, 3 * heads * head_dim))
    probes = [jax.random.normal(k, a.shape) for k, a in zip(
        jax.random.split(keys[2], 4), plain(x, taps, heads, head_dim))]
    return x, taps, probes


@pytest.mark.parametrize("shape", SHAPES)
def test_the_answer_is_the_written_out_steps(shape):
    x, taps, _ = _inputs(*shape)
    heads, head_dim = shape[2:4]
    got = fused(x, taps, heads, head_dim)
    want = plain(x, taps, heads, head_dim)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    # Rounded once: bfloat16 outputs are the float32 answer rounded.
    rounded = fused(x, taps, heads, head_dim, jnp.bfloat16)
    for a, b in zip(rounded[:3], plain(x, taps, heads, head_dim,
                                       jnp.bfloat16)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   b.astype(jnp.float32), rtol=8e-3,
                                   atol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
def test_gradients_in_the_sums_and_the_taps_are_autodiffs(shape):
    x, taps, probes = _inputs(*shape)
    heads, head_dim = shape[2:4]

    def loss(fn):
        return lambda *a: sum(jnp.sum(jnp.sin(o) * p) for o, p in zip(
            fn(*a, heads, head_dim), probes))

    got = jax.grad(loss(fused), argnums=(0, 1))(x, taps)
    want = jax.grad(loss(plain), argnums=(0, 1))(x, taps)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_the_first_rows_see_zeros_before_the_sequence():
    """Row ``t`` reads ``t - 3 .. t`` alone: the first three rows of a
    sequence are the taps on zeros before it, and a change at row 10
    moves rows 10-13 only, in every column."""
    x, taps, _ = _inputs(*SHAPES[0])
    heads, head_dim = SHAPES[0][2:4]
    start = fused(x[:, :3], taps, heads, head_dim)
    for a, b in zip(start[:3], fused(x, taps, heads, head_dim)[:3]):
        np.testing.assert_allclose(a, b[:, :3], rtol=1e-6, atol=1e-7)
    moved = [a - b for a, b in zip(
        fused(x.at[:, 10].add(1.0), taps, heads, head_dim)[:3],
        fused(x, taps, heads, head_dim)[:3])]
    for m in moved:
        assert not np.asarray(m[:, :10]).any()
        assert not np.asarray(m[:, 14:]).any()
        assert np.asarray(m[:, 10:14]).all()


def test_the_interpreter_is_counted_off_the_chip():
    interpret, mosaic = (telemetry.get_registry().counter(
        "ops.kda_conv_trace_total", labels={"mode": mode})
        for mode in ("interpret", "mosaic"))
    before = interpret.value, mosaic.value
    x, taps, probes = _inputs(*SHAPES[0])
    jax.grad(lambda x: jnp.sum(fused(x, taps, 4, 8)[0] * probes[0]))(x)
    # The forward and the backward kernel, each interpreted.
    assert (interpret.value, mosaic.value) == (before[0] + 2, before[1])


def test_more_taps_than_the_carry_reaches_are_refused():
    x, taps, _ = _inputs(*SHAPES[0])
    with pytest.raises(ValueError, match="taps"):
        fused(x, jnp.ones((10, taps.shape[1])), 4, 8)
