"""The gated delta rule's kernels (ops/kda.py, interpreted off the TPU)
against the recurrence one position at a time: values and gradients in
every argument, lengths that are no multiple of the chunk, every gate at
its bound (where the decays of a pair, split over a whole chunk, are no
float32), chunk sizes alike, and the names a rematerialised layer may keep
so that neither kernel runs again."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.ops import kda

BOUND = -5.0            # the published kda_lower_bound


def inputs(seed: int, length: int, *, batch=2, heads=3, key_dim=8, v_dim=6,
           gate=None):
    """Unit keys and scaled unit queries as the mixer makes them; ``g`` in
    (BOUND, 0) a channel, or ``gate`` everywhere."""
    kq, kk, kv, kg, kb = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (batch, length, heads, key_dim))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    g = BOUND * jax.nn.sigmoid(
        2.0 * jax.random.normal(kg, (batch, length, heads, key_dim)))
    if gate is not None:
        g = jnp.full_like(g, gate)
    return (unit(kq) * key_dim ** -0.5, unit(kk),
            jax.random.normal(kv, (batch, length, heads, v_dim)), g,
            jax.nn.sigmoid(jax.random.normal(kb, (batch, length, heads))))


def rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def weighted(rule, probe):
    """A scalar of the rule's whole output, for gradients."""
    return lambda *args: jnp.sum(rule(*args) * probe)


@pytest.mark.parametrize("chunk,sub_block", [(8, 4), (16, 16), (64, 16)])
@pytest.mark.parametrize("length", [64, 37])
def test_the_chunked_rule_is_the_recurrence(chunk, sub_block, length):
    args = inputs(0, length)
    want = kda.kda_recurrent(*args)
    got = kda.kda_chunked(*args, chunk=chunk, sub_block=sub_block)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert rel(got, want) < 2e-5


@pytest.mark.parametrize("chunk,sub_block,length", [(8, 4, 29), (64, 16, 80)])
def test_gradients_in_every_argument(chunk, sub_block, length):
    args = inputs(1, length)
    probe = jax.random.normal(jax.random.PRNGKey(9),
                              kda.kda_recurrent(*args).shape)
    want = jax.grad(weighted(kda.kda_recurrent, probe),
                    argnums=range(5))(*args)
    got = jax.grad(weighted(
        lambda *a: kda.kda_chunked(*a, chunk=chunk, sub_block=sub_block),
        probe), argnums=range(5))(*args)
    for name, a, b in zip("qkvgb", got, want):
        assert rel(a, b) < 1e-4, name


@pytest.mark.parametrize("length", [64, 100])
def test_every_gate_at_the_bound(length):
    """64 steps at -5 sum to -320: ``exp(320)`` is no float32, and the rule
    never forms it.  Values and gradients stay the recurrence's."""
    args = inputs(2, length, gate=BOUND)
    probe = jnp.ones(kda.kda_recurrent(*args).shape)
    want = kda.kda_recurrent(*args)
    got = kda.kda_chunked(*args, chunk=64, sub_block=16)
    assert bool(jnp.all(jnp.isfinite(got))) and rel(got, want) < 2e-4
    want_g = jax.grad(weighted(kda.kda_recurrent, probe),
                      argnums=range(5))(*args)
    got_g = jax.grad(weighted(
        lambda *a: kda.kda_chunked(*a, chunk=64, sub_block=16), probe),
        argnums=range(5))(*args)
    # At the bound nothing outlasts a few steps and the gates' gradient is
    # a thousandth of the others': rounding is a larger share of it.
    for name, a, b in zip("qkvgb", got_g, want_g):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < (1e-3 if name == "g" else 1e-4), name


def test_the_naive_form_fails_at_the_bound():
    """One sub-block a chunk is the naive form, ``K o exp(G)`` against ``K o
    exp(-G)`` over the whole chunk: at the bound it overflows, and away
    from it (a gate of -0.5: ``exp(32)``) it is the recurrence still."""
    mild = inputs(3, 64, gate=-0.5)
    assert rel(kda.kda_chunked(*mild, chunk=64, sub_block=64),
               kda.kda_recurrent(*mild)) < 2e-5
    args = inputs(3, 64, gate=BOUND)
    naive = kda.kda_chunked(*args, chunk=64, sub_block=64)
    assert not bool(jnp.all(jnp.isfinite(naive))) or rel(
        naive, kda.kda_recurrent(*args)) > 1e-2


def test_a_step_of_beta_zero_with_no_decay_leaves_the_state():
    """What the padding relies on: after such steps a query reads what it
    would have read before them."""
    q, k, v, g, beta = inputs(4, 16)
    still = slice(8, 12)
    g = g.at[:, still].set(0.0)
    beta = beta.at[:, still].set(0.0)
    out = kda.kda_chunked(q, k, v, g, beta, chunk=8, sub_block=4)
    keep = np.r_[0:8, 12:16]
    short = kda.kda_chunked(*(a[:, keep] for a in (q, k, v, g, beta)),
                            chunk=8, sub_block=4)
    np.testing.assert_allclose(out[:, keep], short, rtol=1e-4, atol=1e-6)


def test_bfloat16_operands_keep_float32_sums():
    args = inputs(5, 64)
    want = kda.kda_recurrent(*args)
    q, k, v, g, beta = args
    got = kda.kda_chunked(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), g, beta, chunk=16,
                          sub_block=8)
    assert got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), want) < 3e-2


def test_a_chunk_is_whole_sub_blocks():
    with pytest.raises(ValueError, match="whole sub-blocks"):
        kda.kda_chunked(*inputs(6, 16), chunk=12, sub_block=8)


def _kernels(jaxpr) -> list[str]:
    """The names of the Pallas kernels a jaxpr's text calls."""
    return re.findall(r"\bname=(kda_(?:fwd|bwd))\b", str(jaxpr))


def test_a_policy_that_keeps_the_names_runs_each_kernel_once():
    """Under ``save_only_these_names`` the rematerialised forward runs no
    rule kernel: the states, the pseudo-values and the output are kept, so
    the gradient holds one forward kernel and one backward; keeping nothing
    runs the forward kernel again.  The gradients are the same."""
    args = inputs(7, 32)

    def loss(policy):
        rule = jax.checkpoint(
            lambda *a: kda.kda_chunked(*a, chunk=8, sub_block=4),
            policy=policy)
        return lambda *a: jnp.sum(rule(*a) ** 2)

    keep = jax.checkpoint_policies.save_only_these_names(
        *kda.KDA_RESIDUAL_NAMES)
    nothing = jax.checkpoint_policies.nothing_saveable
    kernels = {
        name: sorted(_kernels(jax.make_jaxpr(
            jax.grad(loss(policy), argnums=range(5)))(*args)))
        for name, policy in (("keep", keep), ("nothing", nothing))}
    assert kernels["keep"] == ["kda_bwd", "kda_fwd"]
    assert kernels["nothing"] == ["kda_bwd", "kda_fwd", "kda_fwd"]
    got = jax.grad(loss(keep), argnums=range(5))(*args)
    want = jax.grad(loss(nothing), argnums=range(5))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_primal_call_writes_the_output_alone():
    """Not differentiated, as in the evaluation program, the rule is one
    forward kernel with one result: no state or pseudo-value is written."""
    text = str(jax.make_jaxpr(
        lambda *a: kda.kda_chunked(*a, chunk=8, sub_block=4))(*inputs(8, 16)))
    assert _kernels(text) == ["kda_fwd"]
    assert "kda_states" not in text and "kda_pseudo_values" not in text
