"""EVA chunked linear attention (Zheng et al., ICLR 2023, "Efficient
Attention via Control Variates") with input-independent pooling, as the
EvaByte byte-level model uses it: causal softmax attention that is exact
inside a query's own window and sees every earlier window through one
summary key and value per chunk.

Per head ``h`` with its two learned vectors ``mu_h``, ``phi_h`` and
``s = D ** -0.5``, for chunk ``c`` (``chunk`` consecutive positions)::

    k~_c = sum_m softmax_m(s mu_h . k_m) k_m
    v~_c = sum_m softmax_m(s phi_h . k_m) v_m

and for query ``i`` in window ``w(i) = i // window``, one softmax over two
kinds of key::

    o_i = [sum_{j in E_i} e^{s q_i.k_j} v_j + sum_{c in S_i} e^{s q_i.k~_c} v~_c]
          / [sum_{j in E_i} e^{s q_i.k_j} + sum_{c in S_i} e^{s q_i.k~_c}]

with ``E_i = {j <= i : w(j) = w(i)}`` and ``S_i`` the chunks of the windows
before ``w(i)``.  Windows do not overlap.

How it runs: the summaries are plain ``jax.numpy``; the sequence is folded
into windows on the batch axis; every folded row's keys are the summaries
of all windows but the last (those of its own and later windows hidden by
the key mask) followed by the window's own keys; one call of the flash
kernel with that many ``prefix`` keys (``ops/attention.py``) does the rest,
so no score matrix exists in HBM.  ``impl="dense"`` is the same
mathematics with the scores written out, one folded row at a time: the
oracle the kernel path is tested against.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.ops.attention import _NEG, flash_attention

EVA_IMPLS = ("flash", "dense")


def chunk_summaries(k, v, mu, phi, chunk: int):
    """``k``, ``v``: (B, L, H, D); ``mu``, ``phi``: (H, D).  Returns
    ``(k~, v~)``, each (B, L // chunk, H, D) in the inputs' dtype; the
    pooling weights are computed in float32."""
    B, L, H, D = k.shape
    kc = k.reshape(B, L // chunk, chunk, H, D)
    vc = v.reshape(B, L // chunk, chunk, H, D)

    def pooled(by, values):
        scores = jnp.einsum("bcmhd,hd->bcmh", kc, by.astype(k.dtype),
                            preferred_element_type=jnp.float32) * D ** -0.5
        weights = jax.nn.softmax(scores, axis=2)
        return jnp.einsum("bcmh,bcmhd->bchd", weights.astype(values.dtype),
                          values, preferred_element_type=jnp.float32
                          ).astype(values.dtype)

    return pooled(mu, kc), pooled(phi, vc)


def _dense_rows(q, keys, values, mask, prefix: int):
    """The folded rows with their scores written out, one row at a time:
    ``q`` (N, W, H, D), ``keys``/``values`` (N, prefix + W, H, D), ``mask``
    (N, prefix + W)."""
    W, D = q.shape[1], q.shape[-1]
    causal = (lax.iota(jnp.int32, W)[:, None]
              >= lax.iota(jnp.int32, prefix + W)[None, :] - prefix)

    def one(row):
        qr, kr, vr, mr = row
        logits = jnp.einsum("qhd,khd->hqk", qr, kr,
                            preferred_element_type=jnp.float32) * D ** -0.5
        logits = jnp.where(causal & mr[None, :], logits, _NEG)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(vr.dtype), vr,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    return lax.map(one, (q, keys, values, mask))


def eva_attention(q, k, v, mu, phi, *, window: int, chunk: int,
                  impl: str = "flash", interpret: Optional[bool] = None):
    """``q``, ``k``, ``v``: (B, L, H, D), positions already encoded;
    ``mu``, ``phi``: (H, D).  A sequence no longer than ``window`` is one
    window (plain causal attention; ``mu`` and ``phi`` then go unused)."""
    if impl not in EVA_IMPLS:
        raise ValueError(f"unknown eva impl {impl!r}; use {EVA_IMPLS}")
    B, L, H, D = q.shape
    window = min(window, L)
    if L % window or window % chunk:
        raise ValueError(
            f"eva attention needs whole windows of whole chunks: length {L}, "
            f"window {window}, chunk {chunk}")
    windows, per_window = L // window, window // chunk
    prefix = (windows - 1) * per_window

    def fold(a):
        return a.reshape(B * windows, window, H, D)

    keys, values = fold(k), fold(v)
    mask = None
    if prefix:
        # The last window's summaries are no query's to see.
        summaries = chunk_summaries(k[:, :L - window], v[:, :L - window],
                                    mu, phi, chunk)

        def tiled(s):
            return jnp.broadcast_to(
                s[:, None], (B, windows, prefix, H, D)
            ).reshape(B * windows, prefix, H, D)

        keys = jnp.concatenate([tiled(summaries[0]), keys], axis=1)
        values = jnp.concatenate([tiled(summaries[1]), values], axis=1)
        # Window w sees the chunks of the windows before it.
        seen = (lax.iota(jnp.int32, prefix)[None, :]
                < lax.iota(jnp.int32, windows)[:, None] * per_window)
        mask = jnp.concatenate(
            [seen, jnp.ones((windows, window), bool)], axis=1)
        mask = jnp.tile(mask, (B, 1))
    if impl == "flash":
        # Set where the kernel path is built (at trace time): a program
        # without them ran the written-out scores.
        registry = telemetry.get_registry()
        registry.gauge("eva.keys_per_query_max").set(prefix + window)
        registry.gauge("eva.window").set(window)
        registry.gauge("eva.chunk").set(chunk)
        out = flash_attention(fold(q), keys, values, mask, causal=True,
                              prefix=prefix, interpret=interpret)
    else:
        if mask is None:
            mask = jnp.ones((B * windows, window), bool)
        out = _dense_rows(fold(q), keys, values, mask, prefix)
    return out.reshape(B, L, H, D)
