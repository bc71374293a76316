"""Multi-head latent attention in its training form (DeepSeek-V2,
arXiv:2405.04434): queries and keys/values through low-rank maps with a
norm in the middle, one rotary key shared by all heads, scores over ``d_n +
d_r`` and values of ``d_v``::

    c_q          = RMSNorm(u W_qa)                       q_rank
    [q_n | q_r]  = c_q W_qb                              a head: d_n | d_r
    [c_kv | k_r] = u W_kva                               kv_rank | d_r
    [k_n | v]    = RMSNorm(c_kv) W_kvb                   a head: d_n | d_v
    scores       = ([q_n | rot(q_r)] . [k_n | rot(k_r)]) * scale
    out          = softmax_causal(scores) v W_o

Three variations a caller may ask for.  ``q_rank`` 0: no query rank, ``[q_n |
q_r] = u W_q``.  ``qk_norm``: before the rotation, an RMSNorm with a weight
over each head's ``d_n + d_r`` of ``[q_n | q_r]`` and of ``[k_n | k_r]`` (the
shared ``k_r`` then differs from head to head by its head's norm).
``head_gate``: a head's output times ``sigmoid(u W_gate)``, one gate a head,
before ``W_o``.

``rot`` turns pairs ``(x_i, x_{i + d_r / 2})`` by the position times yarn's
frequencies (``yarn_frequencies``); under yarn ``scale = (d_n + d_r) **
-0.5 * (0.1 mscale_all_dim ln factor + 1) ** 2``, and cos and sin are not
scaled where ``mscale`` equals ``mscale_all_dim``.  Nothing is cached and no
product is absorbed: this is the form that trains.  The core is the flash
kernel of ``ops/attention.py`` (values narrower than the scores, a scale of
the caller's) or, for the tests on the CPU, the written-out scores.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from jax import lax

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.ops.attention import flash_attention
from colearn_federated_learning_tpu.parallel.ring import dense_attention

MLA_IMPLS = ("flash", "dense")


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under yarn, float32: those that
    turn more than ``beta_fast`` times over the original context are kept,
    those that turn less than ``beta_slow`` times are divided by
    ``factor``, and a linear ramp joins them.  ``factor`` 1 gives the plain
    ``theta ** (-2 i / dim)``."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1:
        return plain.astype(np.float32)

    def turns_at(turns: float) -> float:
        """The (fractional) index of the frequency that makes ``turns``
        turns over the original context."""
        return dim * math.log(original_max / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def yarn_scale(factor: float, mscale_all_dim: float) -> float:
    """What yarn multiplies the scores' scale by."""
    if factor <= 1:
        return 1.0
    return (0.1 * mscale_all_dim * math.log(factor) + 1.0) ** 2


def rotate(x, frequencies):
    """``x``: (B, L, H, d) with position on axis 1; pairs ``(x_i, x_{i + d /
    2})`` turned by ``position * frequencies[i]``, in float32."""
    half = x.shape[-1] // 2
    angles = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
              * jnp.asarray(frequencies)[None, :])[None, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


class LatentAttention(nn.Module):
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    # factor (1: plain rotary), original_max_position_embeddings,
    # beta_fast, beta_slow, mscale_all_dim
    yarn: tuple[float, int, float, float, float]
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    impl: str = "flash"
    init_std: float = 0.02
    out_scale: float = 1.0
    qk_norm: bool = False
    head_gate: bool = False

    @nn.compact
    def __call__(self, u):
        """``u``: (B, L, C) in ``dtype``.  Returns (B, L, C)."""
        if self.impl not in MLA_IMPLS:
            raise ValueError(
                f"latent attention runs as {MLA_IMPLS}, not {self.impl!r}")
        B, L, C = u.shape
        H, d_n, d_r, d_v = (self.num_heads, self.nope_dim, self.rope_dim,
                            self.v_dim)
        registry = telemetry.get_registry()     # set on every build
        registry.gauge("mla.heads").set(H)
        registry.gauge("mla.qk_dim").set(d_n + d_r)
        registry.gauge("mla.v_dim").set(d_v)
        registry.gauge("mla.kv_rank").set(self.kv_rank)

        def dense(features, name, std=self.init_std):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=nn.initializers.normal(std),
                            name=name)

        def norm(x, name):
            scale = self.param(name, nn.initializers.ones, (x.shape[-1],))
            return rms_norm(x, scale, self.norm_eps).astype(self.dtype)

        if self.q_rank:
            q = dense(H * (d_n + d_r), "q_b")(
                norm(dense(self.q_rank, "q_a")(u), "q_norm"))
        else:
            q = dense(H * (d_n + d_r), "q")(u)
        q = q.reshape(B, L, H, d_n + d_r)
        kv_a = dense(self.kv_rank + d_r, "kv_a")(u)
        kv = dense(H * (d_n + d_v), "kv_b")(
            norm(kv_a[..., :self.kv_rank], "kv_norm"))
        kv = kv.reshape(B, L, H, d_n + d_v)

        factor, original_max, fast, slow, mscale_all = self.yarn
        frequencies = yarn_frequencies(
            d_r, self.rope_theta, factor, original_max, fast, slow)
        if self.qk_norm:
            k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(
                kv_a[..., None, self.kv_rank:], (B, L, H, d_r))], axis=-1)
            q, k = norm(q, "q_head_norm"), norm(k, "k_head_norm")
            q = jnp.concatenate(
                [q[..., :d_n], rotate(q[..., d_n:], frequencies)], axis=-1)
            k = jnp.concatenate(
                [k[..., :d_n], rotate(k[..., d_n:], frequencies)], axis=-1)
        else:
            q_r = rotate(q[..., d_n:], frequencies)
            k_r = rotate(kv_a[..., None, self.kv_rank:], frequencies)
            q = jnp.concatenate([q[..., :d_n], q_r], axis=-1)
            k = jnp.concatenate(
                [kv[..., :d_n], jnp.broadcast_to(k_r, (B, L, H, d_r))],
                axis=-1)
        v = kv[..., d_n:]
        scale = (d_n + d_r) ** -0.5 * yarn_scale(factor, mscale_all)
        core = flash_attention if self.impl == "flash" else dense_attention
        out = core(q, k, v, causal=True, scale=scale)
        if self.head_gate:
            gate = nn.sigmoid(dense(H, "gate")(u).astype(jnp.float32))
            out = (out * gate[..., None]).astype(self.dtype)
        return dense(C, "out", self.init_std * self.out_scale)(
            out.reshape(B, L, H * d_v))
