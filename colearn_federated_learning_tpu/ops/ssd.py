"""Mamba-2's state-space recurrence, computed chunk by chunk (the "state
space duality" form of Dao and Gu, 2024), in ``jax.numpy`` so that
autodiff gives the backward pass.

Per head ``h`` with a scalar ``A_h < 0``, a step ``dt_t > 0``, an input
``x_t`` (P values) and, shared by the heads of its group, ``B_t`` and
``C_t`` (N values each)::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        (P x N, S_{-1} = 0)
    y_t = S_t C_t

How it runs.  The sequence is cut into chunks of ``chunk`` positions.  With
``a_t = dt_t A_h`` and ``cs_t`` its running sum inside a chunk, position
``s`` reaches position ``t >= s`` of the same chunk with the decay
``exp(cs_t - cs_s)``, so inside a chunk ``y = (L o C B^T)(dt x)`` with ``L``
the lower-triangular matrix of those decays: two matrix products a chunk,
no state.  What a chunk leaves behind is ``sum_s exp(cs_last - cs_s) dt_s
x_s B_s^T``; the states at the chunk boundaries follow from those by a scan
over the chunks (one multiply-add of a P x N state a head and chunk), and a
position reads the state its chunk started from through ``exp(cs_t) C_t``.
The decays, their running sums and the carried state are float32; the
operands of the matrix products are in ``x``'s dtype with float32
accumulation.

A length that is no multiple of ``chunk`` is padded at the end with steps
of ``dt = 0`` (decay 1, no input: the state stands still) and the padding
is cut off the result.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ssd_scan(x, dt, a, b, c, *, chunk: int):
    """``x``: (B, L, H, P); ``dt``: (B, L, H), positive; ``a``: (H,),
    negative; ``b``, ``c``: (B, L, G, N) with ``H`` a multiple of ``G``
    (head ``h`` reads group ``h // (H // G)``).  Returns ``y``: (B, L, H,
    P) in ``x``'s dtype, without the skip term ``D x``."""
    batch, length, heads, width = x.shape
    groups, state = b.shape[-2:]
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    if chunk < 1:
        raise ValueError(f"chunk must be positive, got {chunk}")
    per_group = heads // groups
    pad = -length % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    chunks = (length + pad) // chunk
    dtype = x.dtype

    x = x.reshape(batch, chunks, chunk, groups, per_group, width)
    dt = dt.astype(jnp.float32).reshape(batch, chunks, chunk, groups,
                                        per_group)
    b = b.reshape(batch, chunks, chunk, groups, state)
    c = c.reshape(batch, chunks, chunk, groups, state)
    # (B, chunks, G, R, chunk): the running sum of a_t inside each chunk.
    cs = jnp.cumsum(
        dt * a.astype(jnp.float32).reshape(groups, per_group), axis=2
    ).transpose(0, 1, 3, 4, 2)
    xdt = x.astype(jnp.float32) * dt[..., None]

    # Inside a chunk: (L o C B^T)(dt x).
    reach = cs[..., :, None] - cs[..., None, :]           # t rows, s columns
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, reach, -jnp.inf))
    cb = jnp.einsum("bktgn,bksgn->bkgts", c, b,
                    preferred_element_type=jnp.float32)
    scores = (cb[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bkgrts,bksgrp->bktgrp", scores, xdt.astype(dtype),
                   preferred_element_type=jnp.float32)

    # What each chunk leaves behind, and the state each starts from.
    to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 1, 4, 2, 3)
    left = jnp.einsum("bksgn,bksgrp->bkgrpn", b,
                      (xdt * to_end[..., None]).astype(dtype),
                      preferred_element_type=jnp.float32)
    whole = jnp.exp(cs[..., -1])                          # (B, chunks, G, R)

    def carry_over(start, this):
        left_k, whole_k = this
        return start * whole_k[..., None, None] + left_k, start

    _, starts = lax.scan(
        carry_over,
        jnp.zeros((batch, groups, per_group, width, state), jnp.float32),
        (left.swapaxes(0, 1), whole.swapaxes(0, 1)))
    starts = starts.swapaxes(0, 1)                        # (B, chunks, ...)
    y = y + jnp.einsum(
        "bktgn,bkgrpn->bktgrp", c, starts.astype(dtype),
        preferred_element_type=jnp.float32
    ) * jnp.exp(cs).transpose(0, 1, 4, 2, 3)[..., None]
    y = y.reshape(batch, chunks * chunk, heads, width)[:, :length]
    return y.astype(dtype)

