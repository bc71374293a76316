"""Nemotron-H: a causal decoder whose layers are each a mixer or a
feed-forward part alone, in the order of a pattern string
(``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` on the Hugging Face hub,
``config.json``, ``model_type`` ``nemotron_h``).

Every layer is ``h <- h + Mixer_i(RMSNorm_i(h))`` with ``RMSNorm(x) = x *
rsqrt(mean(x^2) + 1e-5) * g``; no biases but the convolution's; a final
RMSNorm, an untied head, float32 logits over the vocabulary.  The pattern's
letters:

``M``  Mamba-2.  ``[z | xBC | dt] = u W_in`` (widths ``H P``, ``H P + 2 G
       N``, ``H``); ``xBC <- silu(conv(xBC))``, a causal depthwise
       convolution over the last ``conv_kernel`` positions with a bias;
       ``x`` (H, P), ``B`` and ``C`` (G, N) are its parts; ``dt <-
       softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space
       recurrence (``ops/ssd.py``) plus the skip ``D_h x_t``; ``y <-
       RMSNorm(y * silu(z))`` with the norm taken inside each of the ``G``
       groups of channels; ``y W_out``.
``E``  The chip's share of a LatentMoE layer (``models/moe.py``
       ``LatentMoEShare``): a sigmoid router over all the experts in
       float32, experts in a latent space, a shared expert in the full
       width, relu squared.
``*``  Causal grouped-query attention without positions' encoding
       (``models/attention.py`` on the flash kernel of
       ``ops/attention.py``).

The residual stream is in ``dtype`` (the published ``residual_in_fp32`` is
false); norms compute in float32.  What the published config does not fix
and this file chooses: matrices drawn N(0, 0.02), those that write into the
stream divided by the square root of the depth (the published
``rescale_prenorm_residual``); ``dt_bias`` the inverse softplus of a step
drawn log-uniformly from ``time_step_min`` to ``time_step_max`` and held
above ``time_step_floor``; ``A`` drawn from 1 to 16; ``D`` and the norms'
weights 1; the convolution drawn uniformly within ``conv_kernel ** -0.5``.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.models.attention import MultiHeadAttention
from colearn_federated_learning_tpu.models.moe import (
    LatentMoEShare,
    remat_but_for_named,
)
from colearn_federated_learning_tpu.ops.ssd import ssd_scan

RMS_NORM_EPS = 1e-5
INIT_STD = 0.02
TIME_STEP = (0.001, 0.1, 1e-4)          # min, max, floor
LAYER_KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def rms(x):
    """``x * rsqrt(mean(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + RMS_NORM_EPS)


class RMSNorm(nn.Module):
    """Float32 inside and out: the caller rounds it (the router reads it
    unrounded)."""

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms(x) * g


def dt_bias_init(key, shape, dtype=jnp.float32):
    low, high, floor = TIME_STEP
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(high) - math.log(low)) + math.log(low))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))             # softplus^-1(dt)


def a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int
    chunk: int
    out_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        """``u``: (B, L, D) in ``dtype``."""
        B, L, D = u.shape
        H, P, G, N = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, bc = H * P, G * N
        registry = telemetry.get_registry()     # set on every build
        registry.gauge("ssd.heads").set(H)
        registry.gauge("ssd.chunk").set(self.chunk)
        registry.gauge("ssd.state").set(N)
        zxbcdt = nn.Dense(
            2 * inner + 2 * bc + H, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.normal(INIT_STD), name="in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)

        bound = self.conv_kernel ** -0.5
        taps = self.param(
            "conv_kernel",
            lambda key, shape: jax.random.uniform(
                key, shape, jnp.float32, -bound, bound),
            (self.conv_kernel, inner + 2 * bc))
        conv_bias = self.param(
            "conv_bias",
            lambda key, shape: jax.random.uniform(
                key, shape, jnp.float32, -bound, bound),
            (inner + 2 * bc,))
        # Tap j weighs position t - (conv_kernel - 1) + j.
        padded = jnp.pad(xbc, ((0, 0), (self.conv_kernel - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + L] * taps[j].astype(self.dtype)
                   for j in range(self.conv_kernel))
        xbc = nn.silu(conv + conv_bias.astype(self.dtype))
        x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
        x = x.reshape(B, L, H, P)

        dt_bias = self.param("dt_bias", dt_bias_init, (H,))
        a_log = self.param("A_log", a_log_init, (H,))
        skip = self.param("D", nn.initializers.ones, (H,))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        with telemetry.device_scope("ssd"):
            y = ssd_scan(x, dt, -jnp.exp(a_log), b.reshape(B, L, G, N),
                         c.reshape(B, L, G, N), chunk=self.chunk)
        y = y.astype(jnp.float32) + x.astype(jnp.float32) * skip[:, None]

        gate = self.param("norm", nn.initializers.ones, (inner,))
        y = y.reshape(B, L, inner) * nn.silu(z.astype(jnp.float32))
        y = (rms(y.reshape(B, L, G, inner // G)).reshape(B, L, inner)
             * gate).astype(self.dtype)
        return nn.Dense(
            D, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.normal(INIT_STD * self.out_scale),
            name="out_proj")(y)


class HybridBlock(nn.Module):
    """One layer of the stack: its norm, its one mixer, the residual."""
    kind: str
    mixer: dict                     # the mixer's own sizes
    out_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"

    @nn.compact
    def __call__(self, h):
        u32 = RMSNorm(name="norm")(h)
        if self.kind == "mamba":
            out = Mamba2Mixer(out_scale=self.out_scale, dtype=self.dtype,
                              name="mixer", **self.mixer)(
                u32.astype(self.dtype))
        elif self.kind == "moe":
            with telemetry.device_scope("moe"):
                out = LatentMoEShare(
                    out_scale=self.out_scale, dtype=self.dtype,
                    init_std=INIT_STD, name="mixer", **self.mixer)(u32)
        else:
            out = MultiHeadAttention(
                dtype=self.dtype, impl=self.attn_impl, causal=True,
                use_bias=False,
                kernel_init=nn.initializers.normal(INIT_STD),
                out_kernel_init=nn.initializers.normal(
                    INIT_STD * self.out_scale),
                name="mixer", **self.mixer)(u32.astype(self.dtype))
        return h + out.astype(h.dtype)


class NemotronH(nn.Module):
    pattern: str = "MEMEMEM*EME"
    vocab_size: int = 16384
    embed_dim: int = 4096
    # Mamba-2
    mamba_heads: int = 32
    mamba_head_dim: int = 64
    mamba_groups: int = 2
    state_size: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    # LatentMoE, this chip's share
    experts_total: int = 512
    experts_held: tuple[int, int] = (0, 8)
    top_k: int = 22
    latent_dim: int = 1024
    expert_dim: int = 2688
    shared_dim: int = 5376
    routed_scale: float = 5.0
    # attention
    num_heads: int = 8
    num_kv_heads: int = 1
    head_dim: int = 128
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"
    # Rematerialize each layer under autodiff, but for the attention
    # kernel's output and log-sum (models/evabyte.py does the same) and for
    # what the share layer names: its routing, its pairs' rows, its routed
    # rows (models/moe.py SHARE_RESIDUAL_NAMES).
    remat: bool = False

    def _mixer(self, kind: str) -> dict:
        if kind == "mamba":
            return dict(num_heads=self.mamba_heads,
                        head_dim=self.mamba_head_dim,
                        n_groups=self.mamba_groups,
                        state_size=self.state_size,
                        conv_kernel=self.conv_kernel, chunk=self.chunk)
        if kind == "moe":
            return dict(embed_dim=self.embed_dim, latent_dim=self.latent_dim,
                        expert_dim=self.expert_dim,
                        shared_dim=self.shared_dim,
                        experts_total=self.experts_total,
                        experts_held=tuple(self.experts_held),
                        top_k=self.top_k, routed_scale=self.routed_scale)
        return dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim)

    @nn.compact
    def __call__(self, ids, train: bool = False):
        """``ids``: (B, L) token ids.  Float32 logits (B, L, vocabulary):
        position ``i`` predicts token ``i + 1``."""
        unknown = set(self.pattern) - set(LAYER_KINDS)
        if unknown or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: a layer is one of "
                f"{sorted(LAYER_KINDS)}")
        kinds = [LAYER_KINDS[letter] for letter in self.pattern]
        registry = telemetry.get_registry()
        for kind in LAYER_KINDS.values():        # set on every build
            registry.gauge("hybrid.layers", labels={"kind": kind}).set(
                kinds.count(kind))
        h = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     embedding_init=nn.initializers.normal(INIT_STD),
                     name="embed")(ids)
        block_cls = remat_but_for_named(HybridBlock, self.remat)
        out_scale = len(kinds) ** -0.5
        for i, kind in enumerate(kinds):
            # Explicit names pin param paths across remat (models/bert.py).
            h = block_cls(kind, self._mixer(kind), out_scale=out_scale,
                          dtype=self.dtype, attn_impl=self.attn_impl,
                          name=f"layer_{i}")(h)
        with telemetry.device_scope("head"):
            h = RMSNorm(name="norm")(h)
            return nn.Dense(
                self.vocab_size, use_bias=False, dtype=jnp.float32,
                kernel_init=nn.initializers.normal(INIT_STD),
                name="head")(h)
