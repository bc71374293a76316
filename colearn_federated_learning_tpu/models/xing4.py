"""Xing4.0: a causal decoder with latent attention, gated experts, a
residual path of several streams and a multi-token-prediction module
(``XingChen-AGI/Xing4.0-29B-A4B`` on the Hugging Face hub, ``config.json``,
``model_type`` ``xing4_0``).

A token's stream is ``hc_mult`` rows of the width, each a copy of its
embedding at the start.  A layer is two sublayers, attention then
feed-forward, each on the path of ``models/mhc.py``: it reads one mixture
of the rows, ``u``, and writes ``F(RMSNorm(u))`` back into all of them
while a doubly stochastic map mixes them; there is no other residual.
``F`` is latent attention (``models/mla.py``) in the first sublayer; in the
second a gated feed-forward ``W_d (silu(W_g u) * W_u u)`` in the leading
``dense_layers`` layers and this chip's share of a mixture of such experts
beside a shared one (``models/moe.py`` ``GatedMoEShare``) in the others.
After the last layer the rows are summed, a final RMSNorm, an untied head,
float32 logits.

The prediction module (DeepSeek-V3's, arXiv:2412.19437; ``mtp_modules``
1): ``h'_t = [RMSNorm(h_t) ; RMSNorm(Emb(id_{t+1}))] W_eh`` with ``h`` the
summed rows before the final norm, one whole layer with experts on
``hc_mult`` copies of ``h'``, its own final RMSNorm, **the model's own
embedding and head**; its logits at ``t`` predict ``id_{t+2}``.  The token
after a row's last is taken to be id 0, the separator.  The model returns
logits ``(B, L, 1 + mtp_modules, V)``: head ``j`` at position ``i``
predicts token ``i + 1 + j``.

The stream is in ``dtype``; norms, the maps of the residual path and the
router compute in float32.  What the published config does not fix and
this file chooses: matrices drawn N(0, 0.02), those that write into the
stream divided by the square root of twice the depth; the maps' initial
values (``models/mhc.py``); rotary pairs ``(i, i + d / 2)``.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.models import mhc
from colearn_federated_learning_tpu.models.mla import LatentAttention, rms_norm
from colearn_federated_learning_tpu.models.moe import (
    GatedMoEShare,
    gated,
    remat_but_for_named,
)

INIT_STD = 0.02
LAYER_KINDS = ("dense", "moe")


class RMSNorm(nn.Module):
    """Float32 inside and out: the caller rounds it (the router reads it
    unrounded)."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps)


class GatedFfn(nn.Module):
    hidden_dim: int
    out_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        init = nn.initializers.normal(INIT_STD)
        width = u.shape[-1]
        weights = (
            self.param("gate", init, (width, self.hidden_dim)),
            self.param("up", init, (width, self.hidden_dim)),
            self.param("down", nn.initializers.normal(
                INIT_STD * self.out_scale), (self.hidden_dim, width)))
        return gated(u, *(w.astype(self.dtype) for w in weights))


class Xing4Block(nn.Module):
    """One layer: the attention sublayer and the feed-forward sublayer,
    each with its own maps.  ``x``: (B, n, L, C)."""
    kind: str
    attention: dict                 # LatentAttention's sizes
    ffn: dict                       # GatedFfn's or GatedMoEShare's sizes
    maps: dict                      # StreamMaps' sizes
    norm_eps: float = 1e-6
    out_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"

    def sublayer(self, x, name: str, f):
        with telemetry.device_scope("mhc"):
            pre, post, res = mhc.StreamMaps(
                norm_eps=self.norm_eps, init_std=INIT_STD,
                name=f"{name}_maps", **self.maps)(x)
            u32 = RMSNorm(self.norm_eps, name=f"{name}_norm")(
                mhc.read_stream(x, pre))
        out = f(u32)
        with telemetry.device_scope("mhc"):
            return mhc.write_stream(x, res, post, out)

    @nn.compact
    def __call__(self, x):
        def attend(u32):
            with telemetry.device_scope("mla"):
                return LatentAttention(
                    norm_eps=self.norm_eps, dtype=self.dtype,
                    impl=self.attn_impl, init_std=INIT_STD,
                    out_scale=self.out_scale, name="attn",
                    **self.attention)(u32.astype(self.dtype))

        def feed_forward(u32):
            if self.kind == "dense":
                return GatedFfn(out_scale=self.out_scale, dtype=self.dtype,
                                name="ffn", **self.ffn)(
                    u32.astype(self.dtype))
            with telemetry.device_scope("moe"):
                return GatedMoEShare(
                    out_scale=self.out_scale, dtype=self.dtype,
                    init_std=INIT_STD, name="ffn", **self.ffn)(u32)

        x = self.sublayer(x, "attn", attend)
        return self.sublayer(x, "ffn", feed_forward)


class Xing4(nn.Module):
    vocab_size: int = 16384
    embed_dim: int = 3584
    depth: int = 5
    dense_layers: int = 1
    # the residual path
    streams: int = 4
    sinkhorn_iters: int = 20
    sinkhorn_eps: float = 1e-6
    res_clamp: tuple[float, float] = (-30.0, 30.0)
    # latent attention
    num_heads: int = 32
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    yarn: tuple[float, int, float, float, float] = (
        64.0, 4096, 32.0, 1.0, 1.0)
    # feed-forward; the mixture is this chip's share
    ffn_dim: int = 9216
    experts_total: int = 64
    experts_held: tuple[int, int] = (0, 8)
    top_k: int = 4
    expert_dim: int = 1024
    shared_dim: int = 1024
    routed_scale: float = 2.0
    mtp_modules: int = 0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"
    # Rematerialize each layer under autodiff, but for the attention
    # kernel's output and log-sum (models/evabyte.py does the same) and for
    # what the share layer names: its routing, its pairs' rows, its routed
    # rows (models/moe.py SHARE_RESIDUAL_NAMES).
    remat: bool = False

    def _block(self, kind: str, name: str):
        block_cls = remat_but_for_named(Xing4Block, self.remat)
        ffn = dict(hidden_dim=self.ffn_dim) if kind == "dense" else dict(
            embed_dim=self.embed_dim, expert_dim=self.expert_dim,
            shared_dim=self.shared_dim, experts_total=self.experts_total,
            experts_held=tuple(self.experts_held), top_k=self.top_k,
            routed_scale=self.routed_scale)
        # Explicit names pin param paths across remat (models/bert.py).
        return block_cls(
            kind,
            attention=dict(
                num_heads=self.num_heads, q_rank=self.q_rank,
                kv_rank=self.kv_rank, nope_dim=self.nope_dim,
                rope_dim=self.rope_dim, v_dim=self.v_dim,
                rope_theta=self.rope_theta, yarn=tuple(self.yarn)),
            ffn=ffn,
            maps=dict(sinkhorn_iters=self.sinkhorn_iters,
                      eps=self.sinkhorn_eps, clamp=tuple(self.res_clamp)),
            norm_eps=self.norm_eps,
            out_scale=(2 * (self.depth + self.mtp_modules)) ** -0.5,
            dtype=self.dtype, attn_impl=self.attn_impl, name=name)

    @nn.compact
    def __call__(self, ids, train: bool = False):
        """``ids``: (B, L) token ids.  Float32 logits (B, L, 1 +
        mtp_modules, vocabulary)."""
        if not 0 <= self.dense_layers <= self.depth:
            raise ValueError(
                f"{self.dense_layers} leading dense layers of {self.depth}")
        if self.mtp_modules not in (0, 1):
            raise ValueError(
                f"mtp_modules {self.mtp_modules}: one prediction module or "
                "none")
        kinds = ["dense" if i < self.dense_layers else "moe"
                 for i in range(self.depth)]
        registry = telemetry.get_registry()     # set on every build
        for kind in LAYER_KINDS:
            registry.gauge("xing4.layers", labels={"kind": kind}).set(
                kinds.count(kind))
        registry.gauge("mtp.modules").set(self.mtp_modules)
        embed = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(INIT_STD),
                         name="embed")
        head = nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                        kernel_init=nn.initializers.normal(INIT_STD),
                        name="head")
        def copies(h):
            """``h`` (B, L, C) as every row of a stream (B, n, L, C)."""
            return jnp.broadcast_to(
                h[:, None], (h.shape[0], self.streams, *h.shape[1:]))

        x = copies(embed(ids))
        for i, kind in enumerate(kinds):
            x = self._block(kind, f"layer_{i}")(x)
        h = jnp.sum(x.astype(jnp.float32), axis=1)
        with telemetry.device_scope("head"):
            normed = [RMSNorm(self.norm_eps, name="norm")(h)]
        if self.mtp_modules:
            with telemetry.device_scope("mtp"):
                ahead = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
                joined = jnp.concatenate([
                    RMSNorm(self.norm_eps, name="mtp_h_norm")(h),
                    RMSNorm(self.norm_eps, name="mtp_e_norm")(embed(ahead)),
                ], axis=-1).astype(self.dtype)
                h = nn.Dense(
                    self.embed_dim, use_bias=False, dtype=self.dtype,
                    kernel_init=nn.initializers.normal(INIT_STD),
                    name="mtp_proj")(joined)
                x = self._block("moe", "mtp_layer")(copies(h))
                h = jnp.sum(x.astype(jnp.float32), axis=1)
                normed.append(RMSNorm(self.norm_eps, name="mtp_norm")(h))
        # One product for the heads, stacked in front of the positions, and
        # turned afterwards: the chip is free to keep the heads away from
        # the vocabulary's tiles, where two of them would be padded to
        # eight.
        with telemetry.device_scope("head"):
            return jnp.swapaxes(head(jnp.stack(normed, axis=1)), 1, 2)
