"""The gated delta rule with a decay per channel (Kimi delta attention,
arXiv:2510.26692, on DeltaNet's chunked form), computed chunk by chunk in
``jax.numpy`` so that autodiff gives the backward pass.

Per head, with a key ``k_t`` and a query ``q_t`` (K values), a value ``v_t``
(V values), a log-decay a channel ``g_t <= 0`` (K values, ``alpha_t =
exp(g_t)``) and a step ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                (S: K x V, S_{-1} = 0)

The state is *corrected*: what it already answers for ``k_t`` is taken out
before ``v_t`` goes in.  Written as ``S_t = Diag(alpha_t) S_{t-1} + k_t
u_t^T`` the correction is in the pseudo-value ``u_t = beta_t (v_t - S_{t-1}^T
Diag(alpha_t) k_t)``, which depends on every earlier ``u`` of its chunk.

How it runs.  The sequence is cut into chunks of ``chunk`` positions.  With
``G_t`` the running sum of ``g`` inside a chunk (``Gamma = exp(G)``) and
``S`` the state the chunk starts from, position ``i`` reaches position ``t
>= i`` through the decay ``exp(G_t - G_i)`` a channel, and the chunk's
pseudo-values solve a unit lower-triangular system (the WY / UT form)::

    A[t, i] = beta_t sum_c k_tc k_ic exp(G_tc - G_ic)          i <  t
    B[t, i] =        sum_c q_tc k_ic exp(G_tc - G_ic)          i <= t
    [W | U0] = (I + A)^-1 Diag(beta) [K o Gamma | V]
    U        = U0 - W S
    O        = (Q o Gamma) S + B U
    S'       = Diag(Gamma_last) S + (K o exp(G_last - G))^T U

``A``, ``B``, the solve and ``K o exp(G_last - G)`` need no state, so they
are made for all chunks at once; a ``lax.scan`` over the chunks carries
``S`` and hands back each chunk's starting state and ``U`` (two small
products a chunk and head: ``over_chunks``), and the outputs follow from
those, again for all chunks at once.

**The decays of a pair are never split over a whole chunk.**  ``exp(G_t -
G_i)`` is at most 1, but as the product of ``exp(G_t)`` and ``exp(-G_i)``
the second factor is ``exp(320)`` after 64 steps at a gate's bound of -5,
which no float32 holds.  The rows go a sub-block of ``sub_block`` positions
at a time, and a sub-block's decays are taken from the running sum ``R`` at
its middle: its rows carry ``exp(G_t - R)`` and its own columns ``exp(R -
G_i)``, both within ``exp(+-sub_block max|g| / 2)``; the columns before it
carry ``exp(R - G_i) <= 1``, and the columns after it, which the mask
drops, no decay at all.  At 16 positions and a bound of -5 that is
``exp(+-40)``, which float32 and bfloat16 hold with room at both ends (taken
from the sum at the sub-block's *start* the rows would carry ``exp(-80)``,
and a component of ``k`` under 6e-4 times that is flushed to nought, with it
a pair whose decay is near 1: the gradients then read 1e-4 off).
**``sub_block`` times the largest ``|g|`` must stay under about 100.**  The
products stay matrix products over the channels; what is made beside ``k``
is one column panel a sub-block (``chunk / sub_block`` times ``k``), in the
products' precision.

The running sums, the decays, the solve and the carried state are float32;
the operands of the products are in ``v``'s dtype with float32
accumulation.  A length that is no multiple of ``chunk`` is padded at the
end with steps of ``g = 0``, ``beta = 0`` and ``k = 0`` (the state stands
still) and the padding is cut off the result.

The loop over the chunks is a ``jax.custom_vjp`` whose backward reads the
loop's two results and its arguments and nothing else, and the results carry
the names ``KDA_RESIDUAL_NAMES``: under ``jax.checkpoint`` a policy that
keeps them (``save_only_these_names``) leaves the rematerialised forward the
products that need no state, and the loop runs once forward and once,
reversed, backward.  (Under autodiff's own rule for ``lax.scan`` the
rematerialised forward runs the loop again for the rule's private residuals,
whatever is kept.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.scipy.linalg import solve_triangular

from colearn_federated_learning_tpu import telemetry

# What the rule's backward reads of its forward that only the loop over the
# chunks can give: each chunk's starting state and its pseudo-values.
KDA_RESIDUAL_NAMES = ("kda_states", "kda_pseudo_values")


def _sub_block(chunk: int, sub_block: int) -> int:
    if chunk < 1 or sub_block < 1 or chunk % sub_block:
        raise ValueError(
            f"a chunk of {chunk} is not whole sub-blocks of {sub_block}")
    return chunk // sub_block


def _product(dtype, spec, a, b):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def over_chunks(dtype, w, u0, to_end, whole):
    """The loop over the chunks, the chunks in front: ``w``, ``to_end`` (n,
    B, H, C, K), ``u0`` (n, B, H, C, V), ``whole`` (n, B, H, K), float32.
    Returns each chunk's starting state (n, B, H, K, V) and pseudo-values
    ``U = U0 - W S`` (n, B, H, C, V), in ``dtype``: the operands of every
    product they enter.  The carried state is float32.

    A rule of its own for the names' sake: the backward needs the states
    and pseudo-values and nothing else of the loop, so a rematerialised
    layer that keeps ``KDA_RESIDUAL_NAMES`` runs the loop once forward and
    once (reversed, carrying the state's cotangent) backward; what the
    reversed loop leaves is products for all chunks at once."""
    return _over_chunks_fwd(dtype, w, u0, to_end, whole)[0]


def _over_chunks_fwd(dtype, w, u0, to_end, whole):
    product = functools.partial(_product, dtype)

    def carry_over(state, this):
        w_k, u0_k, to_end_k, whole_k = this
        u = u0_k - product("bhck,bhkv->bhcv", w_k, state)
        after = whole_k[..., None] * state + product(
            "bhck,bhcv->bhkv", to_end_k, u)
        return after, (state.astype(dtype), u.astype(dtype))

    _, (states, u) = lax.scan(
        carry_over, jnp.zeros((*w.shape[1:3], w.shape[-1], u0.shape[-1]),
                              jnp.float32), (w, u0, to_end, whole))
    states = checkpoint_name(states, KDA_RESIDUAL_NAMES[0])
    u = checkpoint_name(u, KDA_RESIDUAL_NAMES[1])
    return (states, u), (w, to_end, whole, states, u)


def _over_chunks_bwd(dtype, kept, g):
    w, to_end, whole, states, u = kept
    product = functools.partial(_product, dtype)
    f32 = jnp.float32

    def carry_back(d_after, this):
        """``d_after``: the cotangent of the state the chunk leaves."""
        w_k, to_end_k, whole_k, d_state, d_u = this
        d_u = d_u.astype(f32) + product("bhck,bhkv->bhcv", to_end_k, d_after)
        d_before = (d_state.astype(f32) + whole_k[..., None] * d_after
                    - product("bhck,bhcv->bhkv", w_k, d_u))
        return d_before, (d_after, d_u)

    # The backward pass is traced apart from the forward's scopes.
    with telemetry.device_scope("kda.rule"):
        _, (d_after, d_u) = lax.scan(
            carry_back, jnp.zeros(states.shape[1:], f32),
            (w, to_end, whole, *g), reverse=True)
        return (-product("nbhcv,nbhkv->nbhck", d_u, states), d_u,
                product("nbhcv,nbhkv->nbhck", u, d_after),
                jnp.sum(states.astype(f32) * d_after, axis=-1))


over_chunks.defvjp(_over_chunks_fwd, _over_chunks_bwd)


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, sub_block: int = 16):
    """``q``, ``k``, ``g``: (B, L, H, K); ``v``: (B, L, H, V); ``beta``: (B,
    L, H).  ``g`` is the log of the decay, at most 0.  Returns ``o``: (B, L,
    H, V) in ``v``'s dtype."""
    batch, length, heads, key_dim = k.shape
    dtype = v.dtype
    sub_block = min(sub_block, chunk)
    subs = _sub_block(chunk, sub_block)
    pad = -length % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    chunks = (length + pad) // chunk
    f32 = jnp.float32

    def by_chunk(a):
        """(B, L, H, d) as (B, chunks, H, chunk, d)."""
        return a.reshape(batch, chunks, chunk, heads, -1).swapaxes(2, 3)

    def by_sub(a):
        return a.reshape(*a.shape[:-2], subs, sub_block, a.shape[-1])

    product = functools.partial(_product, dtype)

    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
    beta = by_chunk(beta.astype(f32)[..., None])             # (.., chunk, 1)
    sums = jnp.cumsum(by_chunk(g.astype(f32)), axis=-2)      # G
    k32, q32 = k.astype(f32), q.astype(f32)

    # A sub-block's rows and its column panel, from the sum at its middle.
    middle = by_sub(sums)[..., sub_block // 2, :]            # R: (.., subs, K)
    rows = jnp.exp(by_sub(sums) - middle[..., None, :])
    in_sub = jnp.arange(chunk) // sub_block
    reached = (in_sub[None, :] <= jnp.arange(subs)[:, None])[..., None]
    panel = k32[..., None, :, :] * jnp.exp(jnp.where(
        reached, middle[..., None, :] - sums[..., None, :, :], 0.0))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def pairs(x32, mask):
        """``sum_c x_tc k_ic exp(G_tc - G_ic)`` where ``mask``: (.., chunk,
        chunk)."""
        out = product("...src,...sic->...sri", by_sub(x32) * rows, panel)
        return jnp.where(mask, out.reshape(*out.shape[:-3], chunk, chunk), 0)

    a_mat = pairs(k32, jnp.tril(lower, -1)) * beta
    b_mat = pairs(q32, lower)
    gamma = jnp.exp(sums)
    solved = solve_triangular(
        a_mat, beta * jnp.concatenate([k32 * gamma, v.astype(f32)], axis=-1),
        lower=True, unit_diagonal=True)
    w, u0 = solved[..., :key_dim], solved[..., key_dim:]
    to_end = k32 * jnp.exp(sums[..., -1:, :] - sums)
    whole = gamma[..., -1, :]                                # (B, chunks, H, K)

    states, u = over_chunks(dtype, *(
        a.swapaxes(0, 1) for a in (w, u0, to_end, whole)))
    states, u = states.swapaxes(0, 1), u.swapaxes(0, 1)
    out = (product("bnhck,bnhkv->bnhcv", q32 * gamma, states)
           + product("bnhti,bnhiv->bnhtv", b_mat, u))
    out = out.swapaxes(2, 3).reshape(batch, chunks * chunk, heads, -1)
    return out[:, :length].astype(dtype)


def kda_recurrent(q, k, v, g, beta):
    """The same rule one position at a time, float32: what the chunked form
    is held to (``tests/test_kda.py``)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))

    def step(state, this):
        q_t, k_t, v_t, g_t, beta_t = this                   # (B, H, ..)
        state = state * jnp.exp(g_t)[..., None]
        u = beta_t[..., None] * (
            v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state,
                             precision=lax.Precision.HIGHEST))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state,
                                 precision=lax.Precision.HIGHEST)

    batch, _, heads, key_dim = k.shape
    _, out = lax.scan(
        step, jnp.zeros((batch, heads, key_dim, v.shape[-1]), f32),
        tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return out.swapaxes(0, 1)
