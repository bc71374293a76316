"""BERT-style text classifier — BASELINE config #4 ("FedAvg BERT-base on
AG-News, 50 text clients").

A from-scratch encoder (token + learned position embeddings, post-LN
transformer blocks, masked mean pooling, classification head).  Attention
and MLPs are plain ``nn.Dense``/einsum matmuls — large, batched, and
bfloat16-ready so XLA tiles them onto the MXU.  Token id 0 is padding and
is masked out of both attention and pooling.  Sequence length is static
(config.seq_len), so the whole model jits with no dynamic shapes.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu.models.attention import MultiHeadAttention


class TransformerBlock(nn.Module):
    embed_dim: int
    num_heads: int
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "dense"
    attn_axis_name: Optional[str] = None
    num_experts: int = 0              # > 0: MoE FFN (models/moe.py)

    @nn.compact
    def __call__(self, x, pad_mask):
        # Post-LN (BERT-style): sublayer -> residual -> LayerNorm.
        attn = MultiHeadAttention(
            num_heads=self.num_heads, dtype=self.dtype,
            impl=self.attn_impl, axis_name=self.attn_axis_name,
        )(x, pad_mask)
        x = nn.LayerNorm(dtype=self.dtype)(x + attn)
        if self.num_experts > 0:
            from colearn_federated_learning_tpu.models.moe import MoEFfn

            h = MoEFfn(self.embed_dim, self.num_experts,
                       mlp_ratio=self.mlp_ratio, dtype=self.dtype)(
                x, token_mask=pad_mask
            )
        else:
            h = nn.Dense(self.embed_dim * self.mlp_ratio, dtype=self.dtype)(x)
            h = nn.gelu(h)
            h = nn.Dense(self.embed_dim, dtype=self.dtype)(h)
        return nn.LayerNorm(dtype=self.dtype)(x + h)


class BertClassifier(nn.Module):
    num_classes: int = 4
    vocab_size: int = 30522
    embed_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    max_len: int = 128
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "dense"
    seq_axis_name: Optional[str] = None
    # > 0 turns every other block (odd index; block 0 when depth == 1)
    # into a mixture-of-experts block — the GShard interleaving, so deep
    # models keep dense MLPs between MoE layers.
    num_experts: int = 0
    # Rematerialize each block under autodiff (activation HBM ∝ depth
    # becomes ∝ 1 at the cost of one extra forward per block).
    remat: bool = False
    # Leaves read only by gathering rows of ``ids``, and the field that
    # sizes each: fed/local.py trains them on the rows a round can touch.
    gathered_tables = {"Embed_0/embedding": "vocab_size"}

    @nn.compact
    def __call__(self, ids, train: bool = False):
        """``ids``: (B, L) token ids.

        Sequence parallelism: with ``seq_axis_name`` set (and
        ``attn_impl="ring"``) the module runs inside ``shard_map`` on a
        local (B, L/S) shard — position embeddings are sliced at this
        shard's GLOBAL offset, attention rings over the axis, and the
        masked-mean pooling finishes with a psum so logits come out
        replicated across the sequence axis.
        """
        B, L = ids.shape
        sp = self.seq_axis_name
        pad_mask = (ids != 0)                                  # (B, L)
        tok = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype)(ids)
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02), (1, self.max_len, self.embed_dim)
        )
        if sp is not None:
            offset = jax.lax.axis_index(sp) * L
            pos_l = jax.lax.dynamic_slice_in_dim(pos, offset, L, axis=1)
        else:
            pos_l = pos[:, :L]
        x = tok + pos_l.astype(self.dtype)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        block_cls = (
            nn.remat(TransformerBlock) if self.remat else TransformerBlock
        )
        for i in range(self.depth):
            moe_here = self.num_experts > 0 and (
                i % 2 == 1 or self.depth == 1
            )
            # Explicit names pin the param paths: nn.remat's auto-prefix
            # ("CheckpointTransformerBlock_i") would otherwise fork the
            # pytree from the non-remat twin, breaking checkpoints, wire
            # payloads and the TP partition rules.
            x = block_cls(self.embed_dim, self.num_heads, dtype=self.dtype,
                          attn_impl=self.attn_impl,
                          attn_axis_name=sp,
                          num_experts=self.num_experts if moe_here else 0,
                          name=f"TransformerBlock_{i}")(
                x, pad_mask
            )
        # Masked mean pooling (no [CLS] convention in the synthetic corpus);
        # under SP the token sums finish with a psum over the sequence axis
        # whose grad convention pairs with the trainer's pmean (see
        # parallel/collectives.py).
        m = pad_mask[..., None].astype(jnp.float32)
        sum_x = (x.astype(jnp.float32) * m).sum(1)
        sum_m = m.sum(1)
        if sp is not None:
            from colearn_federated_learning_tpu.parallel.collectives import (
                psum_for_grad_pmean,
            )

            sum_x = psum_for_grad_pmean(sum_x, sp)
            sum_m = jax.lax.psum(sum_m, sp)  # mask: no grad
        pooled = sum_x / jnp.maximum(sum_m, 1.0)
        logits = nn.Dense(self.num_classes, dtype=jnp.float32)(pooled)
        return logits
