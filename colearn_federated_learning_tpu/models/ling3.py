"""Ling-3.0: a causal decoder whose layers mix by Kimi delta attention or by
latent attention, by layer index, and feed forward through a dense gated
block or through gated experts, by layer index
(``inclusionAI/Ling-3.0-flash-VL`` on the Hugging Face hub, ``config.json``;
the language model alone: no vision tower, no prediction module).

Every layer is ``h <- h + Mixer_i(RMSNorm(h))`` then ``h <- h +
FeedForward_i(RMSNorm(h))``; ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``;
no biases; a final RMSNorm, an untied head, float32 logits.  With ``i`` the
layer's index in the published stack (``first_layer`` is that of the first
layer held here):

mixer       latent attention where ``(i + 1) % layer_group_size == 0``, else
            Kimi delta attention (KDA).
feed-forward  ``W_d (silu(W_g u) * W_u u)`` at ``ffn_dim`` in the first
            ``dense_layers`` layers held; in the others this chip's share of a
            mixture of such experts beside a shared one (``models/moe.py``
            ``GatedMoEShare``), chosen within ``topk_group`` of ``n_group``
            groups, under the layer's clamps if it has any.

**KDA** (Kimi Linear, arXiv:2510.26692; ``kda_safe_gate``, ``no_kda_lora``,
``linear_silu``), ``H`` heads of ``d`` (``head_dim``) for keys and values
alike, per head::

    q~, k~, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
    q, k      = d^-1/2 q~ / |q~|_2 ,  k~ / |k~|_2
    g_t       = lower_bound sigmoid(exp(A_log_h) (u W_f + dt_bias))
    beta_t    = sigmoid(u W_beta)
    S_t       = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t       = S_t^T q_t
    y         = W_o [RMSNorm_head(o_t) * sigmoid(u W_g)]

``conv`` is a causal depthwise convolution over the last ``conv_kernel``
positions, without a bias; ``g`` is a log-decay a channel in (``lower_bound``,
0); ``beta`` one a head; the norm is over a head's ``d`` with one weight
shared by the heads; the output gate is a channel's.  The five maps of ``u``
are one product (``in_proj``: q, k, v, f, the gate, beta) whose float32 sums
the convolution, the norms and the gates read unrounded.  The convolution,
its SiLU and the norms of q and k are one kernel each way
(``ops/kda_conv.py``); the rule runs chunk by chunk (``ops/kda.py``).

**Latent attention** (``models/mla.py``): no query rank, an RMSNorm over
each head's scores' width of q and of k before plain rotary on the last
``rope_dim``, one output gate a head.

The stream is in ``dtype``; norms, gates, decays and the router compute in
float32.  What the published config does not fix and this file chooses:
matrices drawn N(0, 0.02), those that write into the stream divided by the
square root of twice the depth; ``A_log`` and ``dt_bias`` as
``models/nemotron_h.py`` draws its Mamba mixer's; the convolution drawn
uniformly within ``conv_kernel ** -0.5``; rotary pairs ``(i, i + d / 2)``;
1e-6 under the root of q's and k's norms.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.models.mla import LatentAttention, rms_norm
from colearn_federated_learning_tpu.models.moe import (
    GatedMoEShare,
    remat_but_for_named,
)
from colearn_federated_learning_tpu.models.nemotron_h import (
    a_log_init,
    dt_bias_init,
)
from colearn_federated_learning_tpu.models.xing4 import GatedFfn, RMSNorm
from colearn_federated_learning_tpu.ops.kda import (
    KDA_RESIDUAL_NAMES,
    kda_chunked,
)
from colearn_federated_learning_tpu.ops.kda_conv import conv_qkv

INIT_STD = 0.02
L2_EPS = 1e-6
MIXER_KINDS = ("kda", "mla")
FFN_KINDS = ("dense", "moe")


def mixer_kind(published_index: int, layer_group_size: int) -> str:
    return "mla" if (published_index + 1) % layer_group_size == 0 else "kda"


class KdaMixer(nn.Module):
    num_heads: int
    head_dim: int
    conv_kernel: int
    chunk: int
    lower_bound: float
    norm_eps: float = 1e-6
    out_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        """``u``: (B, L, D) in ``dtype``."""
        B, L, D = u.shape
        H, d = self.num_heads, self.head_dim
        inner = H * d
        registry = telemetry.get_registry()     # set on every build
        registry.gauge("kda.heads").set(H)
        registry.gauge("kda.chunk").set(self.chunk)
        # The maps' float32 sums are kept: the convolution, the norms of q
        # and k and the gates read them unrounded, and what reaches the
        # rule is rounded once.
        kernel = self.param("in_proj", nn.initializers.normal(INIT_STD),
                            (D, 5 * inner + H))
        projected = jnp.dot(u, kernel.astype(self.dtype),
                            preferred_element_type=jnp.float32)

        with telemetry.device_scope("kda.conv"):
            bound = self.conv_kernel ** -0.5
            taps = self.param(
                "conv_kernel",
                lambda key, shape: jax.random.uniform(
                    key, shape, jnp.float32, -bound, bound),
                (self.conv_kernel, 3 * inner))
            q, k, v, rest = conv_qkv(projected, taps, heads=H, head_dim=d,
                                     eps=L2_EPS, dtype=self.dtype)
            q, k, v = (a.reshape(B, L, H, d) for a in (q, k, v))
        f, gate, beta = jnp.split(rest, [inner, 2 * inner], axis=-1)

        with telemetry.device_scope("kda.gate"):
            dt_bias = self.param("dt_bias", dt_bias_init, (inner,))
            a_log = self.param("A_log", a_log_init, (H,))
            g = self.lower_bound * nn.sigmoid(
                jnp.exp(a_log)[:, None]
                * (f + dt_bias).reshape(B, L, H, d))
            beta = nn.sigmoid(beta)
        with telemetry.device_scope("kda.rule"):
            o = kda_chunked(q, k, v, g, beta, chunk=self.chunk)
        with telemetry.device_scope("kda.gate"):
            scale = self.param("norm", nn.initializers.ones, (d,))
            o = rms_norm(o, scale, self.norm_eps).reshape(B, L, inner)
            o = (o * nn.sigmoid(gate)).astype(self.dtype)
        return nn.Dense(
            D, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.normal(INIT_STD * self.out_scale),
            name="out_proj")(o)


class Ling3Block(nn.Module):
    """One layer: its mixer and its feed-forward, each after its own norm,
    each added to the stream."""
    mixer_kind: str
    ffn_kind: str
    mixer: dict                     # KdaMixer's or LatentAttention's sizes
    ffn: dict                       # GatedFfn's or GatedMoEShare's sizes
    norm_eps: float = 1e-6
    out_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"

    @nn.compact
    def __call__(self, h):
        u = RMSNorm(self.norm_eps, name="mixer_norm")(h).astype(self.dtype)
        if self.mixer_kind == "kda":
            with telemetry.device_scope("kda"):
                out = KdaMixer(norm_eps=self.norm_eps,
                               out_scale=self.out_scale, dtype=self.dtype,
                               name="mixer", **self.mixer)(u)
        else:
            with telemetry.device_scope("mla"):
                out = LatentAttention(
                    norm_eps=self.norm_eps, dtype=self.dtype,
                    impl=self.attn_impl, init_std=INIT_STD,
                    out_scale=self.out_scale, name="mixer", **self.mixer)(u)
        h = h + out.astype(h.dtype)
        u32 = RMSNorm(self.norm_eps, name="ffn_norm")(h)
        if self.ffn_kind == "dense":
            out = GatedFfn(out_scale=self.out_scale, dtype=self.dtype,
                           name="ffn", **self.ffn)(u32.astype(self.dtype))
        else:
            with telemetry.device_scope("moe"):
                out = GatedMoEShare(
                    out_scale=self.out_scale, dtype=self.dtype,
                    init_std=INIT_STD, name="ffn", **self.ffn)(u32)
        return h + out.astype(h.dtype)


class Ling3(nn.Module):
    vocab_size: int = 19648
    embed_dim: int = 2560
    depth: int = 7
    first_layer: int = 1            # the published index of the first held
    layer_group_size: int = 6
    dense_layers: int = 1
    num_heads: int = 32
    head_dim: int = 128
    # Kimi delta attention
    conv_kernel: int = 4
    chunk: int = 64
    lower_bound: float = -5.0
    # latent attention
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 6e6
    # feed-forward; the mixture is this chip's share
    ffn_dim: int = 6144
    experts_total: int = 512
    experts_held: tuple[int, int] = (0, 8)
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    expert_dim: int = 768
    shared_dim: int = 768
    routed_scale: float = 2.5
    token_block: int = 4096
    row_tile: int = 4096
    # a held layer's clamps on its experts and its shared expert; () none
    expert_limits: tuple[float, ...] = ()
    shared_limits: tuple[float, ...] = ()
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"
    # Rematerialize each layer under autodiff, but for the attention
    # kernel's output and log-sum, what the share layer names and what the
    # delta rule names (models/moe.py ``remat_but_for_named``).
    remat: bool = False

    def _limit(self, limits, layer: int) -> float:
        if limits and len(limits) != self.depth:
            raise ValueError(
                f"{len(limits)} clamps for {self.depth} layers")
        return float(limits[layer]) if limits else 0.0

    def _block(self, layer: int, block_cls):
        kind = mixer_kind(self.first_layer + layer, self.layer_group_size)
        if kind == "kda":
            mixer = dict(num_heads=self.num_heads, head_dim=self.head_dim,
                         conv_kernel=self.conv_kernel, chunk=self.chunk,
                         lower_bound=self.lower_bound)
        else:
            mixer = dict(
                num_heads=self.num_heads, q_rank=0, kv_rank=self.kv_rank,
                nope_dim=self.nope_dim, rope_dim=self.rope_dim,
                v_dim=self.v_dim, rope_theta=self.rope_theta,
                yarn=(1.0, 0, 0.0, 0.0, 0.0), qk_norm=True, head_gate=True)
        if layer < self.dense_layers:
            ffn_kind, ffn = "dense", dict(hidden_dim=self.ffn_dim)
        else:
            ffn_kind, ffn = "moe", dict(
                embed_dim=self.embed_dim, expert_dim=self.expert_dim,
                shared_dim=self.shared_dim, experts_total=self.experts_total,
                experts_held=tuple(self.experts_held), top_k=self.top_k,
                routed_scale=self.routed_scale, n_group=self.n_group,
                topk_group=self.topk_group, token_block=self.token_block,
                row_tile=self.row_tile,
                expert_limit=self._limit(self.expert_limits, layer),
                shared_limit=self._limit(self.shared_limits, layer))
        # Explicit names pin param paths across remat (models/bert.py).
        return kind, ffn_kind, block_cls(
            kind, ffn_kind, mixer=mixer, ffn=ffn, norm_eps=self.norm_eps,
            out_scale=(2 * self.depth) ** -0.5, dtype=self.dtype,
            attn_impl=self.attn_impl, name=f"layer_{layer}")

    @nn.compact
    def __call__(self, ids, train: bool = False):
        """``ids``: (B, L) token ids.  Float32 logits (B, L, vocabulary):
        position ``i`` predicts token ``i + 1``."""
        if not 0 <= self.dense_layers <= self.depth:
            raise ValueError(
                f"{self.dense_layers} leading dense layers of {self.depth}")
        h = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     embedding_init=nn.initializers.normal(INIT_STD),
                     name="embed")(ids)
        # Beside the flash kernel's and the share layer's names, the delta
        # rule's: each chunk's starting state and pseudo-values.
        block_cls = remat_but_for_named(Ling3Block, self.remat,
                                        KDA_RESIDUAL_NAMES)
        kinds = []
        for layer in range(self.depth):
            mixer, ffn, block = self._block(layer, block_cls)
            kinds += [mixer, ffn]
            h = block(h)
        registry = telemetry.get_registry()     # set on every build
        for kind in MIXER_KINDS + FFN_KINDS:
            registry.gauge("ling3.layers", labels={"kind": kind}).set(
                kinds.count(kind))
        registry.gauge("kda.layers").set(kinds.count("kda"))
        registry.gauge("kda.remat_saved_arrays").set(
            len(KDA_RESIDUAL_NAMES) if self.remat else 0)
        with telemetry.device_scope("head"):
            h = RMSNorm(self.norm_eps, name="norm")(h)
            return nn.Dense(
                self.vocab_size, use_bias=False, dtype=jnp.float32,
                kernel_init=nn.initializers.normal(INIT_STD),
                name="head")(h)
