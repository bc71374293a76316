"""Ring attention: sequence parallelism over a mesh axis.

Long-context attention whose K/V blocks rotate around the mesh axis via
``jax.lax.ppermute`` while each device keeps its local Q block resident —
attention over a sequence S·L long costs each chip S steps of (L × L)
blockwise attention plus one neighbour-to-neighbour ICI transfer per step,
instead of materialising the full (S·L)² score matrix anywhere.  Softmax is
accumulated online (running max ``m``, normaliser ``l``, weighted-value
accumulator ``acc`` in float32), the same rescaling recurrence as
flash attention (ops/attention.py) applied across devices instead of across
VMEM tiles.

The reference has no long-context path at all (SURVEY.md §5 "Long-context /
SP: absent"); this is the TPU-native capability the rebuild adds so the
BERT/ViT federated configs scale past one chip's HBM.

Must be called inside ``shard_map`` with the sequence dimension sharded over
``axis_name``.  Works on any backend (tests run it on the 8-device virtual
CPU mesh; on TPU the ppermute rides ICI).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30  # additive mask value; big-negative not -inf so exp() is exact 0


def _block_attn(q, k, v, bias, m, l, acc, scale):
    """One blockwise online-softmax update.

    q: (B, Lq, H, D), k/v: (B, Lk, H, D), bias: (B, 1|H, Lq, Lk) additive.
    Carries m, l: (B, H, Lq) and acc: (B, Lq, H, D), all float32.
    """
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        logits = logits + bias
    m_new = jnp.maximum(m, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])            # (B, H, Lq, Lk)
    # Fully-masked blocks: m_new sits at the _NEG floor, making exp(0)=1 for
    # masked entries; force those to 0 so padding never contributes.
    p = jnp.where(logits > 0.5 * _NEG, p, 0.0)
    corr = jnp.exp(m - m_new)                          # (B, H, Lq)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: Optional[jax.Array] = None,
    *,
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Attention with the sequence axis sharded over ``axis_name``.

    Args:
      q, k, v: local blocks ``(B, L_local, H, D)`` — the global sequence is
        ``axis_size * L_local`` long, laid out in axis-index order.
      kv_mask: optional ``(B, L_local)`` bool; False = padding key (masked
        out everywhere, like BERT's padding mask).
      causal: mask by GLOBAL position (query attends to keys ≤ its global
        index), for decoder-style long-context models.

    Returns the local output block ``(B, L_local, H, D)`` in q's dtype.
    Fully-masked query rows return 0.
    """
    s = lax.psum(1, axis_name)                  # devices on the ring
    my = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    perm = [(j, (j + 1) % s) for j in range(s)]

    qf = q.astype(jnp.float32)
    q_pos = my * Lq + lax.iota(jnp.int32, Lq) if causal else None

    def attend(i, m, l, acc, k_blk, v_blk, mask_blk):
        # After i rotations device ``my`` holds the block ORIGINATED by
        # device (my - i) mod s; global key positions follow from that.
        src = (my - i) % s
        bias = None
        if mask_blk is not None:
            bias = jnp.where(mask_blk, 0.0, _NEG)[:, None, None, :]
        if causal:
            k_pos = src * Lk + lax.iota(jnp.int32, Lk)
            cmask = (q_pos[:, None] >= k_pos[None, :]).astype(jnp.float32)
            cbias = (1.0 - cmask) * _NEG                    # (Lq, Lk)
            bias = cbias[None, None] if bias is None else bias + cbias[None, None]
        return _block_attn(qf, k_blk, v_blk, bias, m, l, acc, scale)

    def step(i, carry):
        # Rotation LEADS the step so the last iteration does not pay a
        # final, discarded neighbour transfer (1/s of total ring traffic).
        m, l, acc, k_blk, v_blk, mask_blk = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if mask_blk is not None:
            mask_blk = lax.ppermute(mask_blk, axis_name, perm)
        m, l, acc = attend(i, m, l, acc, k_blk, v_blk, mask_blk)
        return m, l, acc, k_blk, v_blk, mask_blk

    m0 = jnp.full((B, H, Lq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    acc0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    m0, l0, acc0 = attend(0, m0, l0, acc0, k, v, kv_mask)   # home block
    m, l, acc, _, _, _ = lax.fori_loop(
        1, s, step, (m0, l0, acc0, k, v, kv_mask)
    )
    out = acc / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: Optional[jax.Array] = None,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-device reference with the same (B, L, H, D) signature — the
    numerics oracle ring/flash attention are tested against, and the
    ``attn_impl="dense"`` core in models/attention.py.  ``v`` may have a
    width of its own; ``scale`` multiplies the scores in place of ``D **
    -0.5``."""
    Lq, Lk = q.shape[1], k.shape[1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits / (q.shape[-1] ** 0.5) if scale is None else logits * scale
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :], logits, _NEG)
    if causal:
        qp = lax.iota(jnp.int32, Lq)[:, None]
        kp = lax.iota(jnp.int32, Lk)[None, :]
        logits = jnp.where((qp >= kp)[None, None], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows: softmax over all-_NEG is uniform; zero them to
    # match ring_attention's convention.
    if kv_mask is not None:
        any_key = jnp.any(kv_mask, axis=-1)[:, None, None, None]
        p = p * any_key
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))
    return out.astype(q.dtype)
