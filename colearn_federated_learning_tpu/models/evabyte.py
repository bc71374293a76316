"""EvaByte: a causal byte-level decoder with EVA chunked linear attention
(``EvaByte/EvaByte`` on the Hugging Face hub, ``config.json``; attention:
``ops/eva.py``).

Llama-shaped blocks over a float32 residual stream: ``h <- h +
Attn(RMSNorm(h))``, ``h <- h + W_down(silu(W_gate u) * W_up u)`` with ``u =
RMSNorm(h)``; RMSNorm with eps 1e-5 and weight ``1 + g``
(``norm_add_unit_offset``); no biases; rotary positions on q and k (the
half-split form: a position rotates coordinate ``i`` against ``i + D/2``);
per head two learned vectors ``mu``, ``phi`` that pool a chunk's keys and
values into one summary each.  The head is one linear map to
``num_pred_heads * vocab_size`` float32 logits: head ``j`` at position ``i``
predicts byte ``i + 1 + j``.  Everything but the residual sum
(``fp32_skip_add``) and the logits (``fp32_logits``) computes in ``dtype``.

What the published ``config.json`` does not fix is written here as the
model's public code is recalled: the pooling form (two softmax poolings by
``mu`` and ``phi``), windows that do not overlap, a final RMSNorm before
the head; and chosen: matrices drawn N(0, ``init_std`` = 0.01275), ``mu``
and ``phi`` N(0, 1).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.ops.attention import FLASH_RESIDUAL_NAMES
from colearn_federated_learning_tpu.ops.eva import eva_attention

RMS_NORM_EPS = 1e-5
INIT_STD = 0.01275


def rotary(x, theta: float):
    """``x``: (B, L, H, D) at positions 0..L-1; float32 inside."""
    L, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * (1 + g)``, float32 in, ``dtype`` out."""
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x * jnp.reciprocal(jnp.sqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + RMS_NORM_EPS))
        return (x * (1.0 + g)).astype(self.dtype)


class EvaBlock(nn.Module):
    num_heads: int
    ffn_dim: int
    window: int
    chunk: int
    rope_theta: float
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"

    @nn.compact
    def __call__(self, h):
        """``h``: (B, L, D) float32, and so is what comes back."""
        D = h.shape[-1]
        head_dim = D // self.num_heads
        init = nn.initializers.normal(INIT_STD)

        def heads(name):
            return nn.DenseGeneral(
                features=(self.num_heads, head_dim), use_bias=False,
                dtype=self.dtype, kernel_init=init, name=name)

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=init, name=name)

        u = RMSNorm(dtype=self.dtype, name="attn_norm")(h)
        q = rotary(heads("query")(u), self.rope_theta)
        k = rotary(heads("key")(u), self.rope_theta)
        v = heads("value")(u)
        pooling = nn.initializers.normal(1.0)
        mu = self.param("mu", pooling, (self.num_heads, head_dim))
        phi = self.param("phi", pooling, (self.num_heads, head_dim))
        a = eva_attention(q, k, v, mu, phi, window=self.window,
                          chunk=self.chunk, impl=self.attn_impl)
        a = nn.DenseGeneral(features=D, axis=(-2, -1), use_bias=False,
                            dtype=self.dtype, kernel_init=init,
                            name="out")(a)
        h = h + a.astype(jnp.float32)
        u = RMSNorm(dtype=self.dtype, name="ffn_norm")(h)
        f = dense(D, "down")(
            nn.silu(dense(self.ffn_dim, "gate")(u))
            * dense(self.ffn_dim, "up")(u))
        return h + f.astype(jnp.float32)


class EvaByte(nn.Module):
    vocab_size: int = 320
    embed_dim: int = 4096
    depth: int = 32
    num_heads: int = 32
    ffn_dim: int = 11008
    window: int = 2048
    chunk: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"
    # Rematerialize each block under autodiff, but for the attention
    # kernel's output and log-sum: kept (136 MB a layer at the published
    # widths), they spare the backward a second ``flash_fwd``, the slowest
    # unit of the block to repeat.
    remat: bool = False

    @nn.compact
    def __call__(self, ids, train: bool = False):
        """``ids``: (B, L) byte ids.  Float32 logits (B, L,
        ``num_pred_heads``, ``vocab_size``)."""
        if self.embed_dim % self.num_heads:
            raise ValueError(f"embed dim {self.embed_dim} not divisible by "
                             f"{self.num_heads} heads")
        B, L = ids.shape
        h = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     embedding_init=nn.initializers.normal(INIT_STD),
                     name="embed")(ids).astype(jnp.float32)
        block_cls = EvaBlock
        if self.remat:
            block_cls = nn.remat(
                EvaBlock, policy=jax.checkpoint_policies.save_only_these_names(
                    *FLASH_RESIDUAL_NAMES))
        # Set at trace time, on every build: 0 says nothing is rematerialised.
        telemetry.get_registry().gauge("evabyte.remat_saved_arrays").set(
            len(FLASH_RESIDUAL_NAMES) if self.remat else 0)
        for i in range(self.depth):
            # Explicit names pin param paths across remat (models/bert.py).
            h = block_cls(self.num_heads, self.ffn_dim, self.window,
                          self.chunk, self.rope_theta, dtype=self.dtype,
                          attn_impl=self.attn_impl, name=f"block_{i}")(h)
        with telemetry.device_scope("head"):
            h = RMSNorm(dtype=jnp.float32, name="norm")(h)
            logits = nn.Dense(self.num_pred_heads * self.vocab_size,
                              use_bias=False, dtype=jnp.float32,
                              kernel_init=nn.initializers.normal(INIT_STD),
                              name="head")(h)
            return logits.reshape(B, L, self.num_pred_heads,
                                  self.vocab_size)
