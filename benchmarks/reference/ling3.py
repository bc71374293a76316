"""Plain reference for the ``ling3`` family: forward pass and loss in
float32 ``jax.numpy``, written from the layers' equations.

The published model is ``inclusionAI/Ling-3.0-flash-VL`` (``config.json``),
its language model alone.  No modelling code is on the machine: the
equations below are what this file implements, each name a key of the
published config, and the configuration file lists under ``assumed`` every
choice the keys do not fix.  The delta rule is Kimi Linear's
(arXiv:2510.26692), latent attention and the router's group-limited choice
DeepSeek-V2/V3's (arXiv:2405.04434, arXiv:2412.19437), as recalled.

Pre-norm residual blocks, ``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps)
* w``: ``h <- h + Mixer_i(RMSNorm(h))``, ``h <- h + FeedForward_i(RMSNorm(h))``;
a final RMSNorm, the untied head.  Layer ``i`` of the published stack (the
first held here is ``first_layer``) mixes by latent attention where ``(i +
1) % layer_group_size == 0`` and by Kimi delta attention elsewhere; the
first ``dense_layers`` layers held feed forward through ``W_d (silu(W_g u) *
W_u u)`` at ``intermediate_size``, the others through the experts.

**Kimi delta attention**, ``H`` heads of ``d = head_dim`` for keys and
values alike (``num_kv_heads_for_linear_attn`` 0: as many as query heads),
``no_kda_lora``: whole matrices; **one position at a time**::

    q~, k~, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
    q, k      = d^-1/2 q~ / |q~|_2 ,  k~ / |k~|_2
    g_t       = kda_lower_bound sigmoid(exp(A_log_h) (u W_f + dt_bias))
    beta_t    = sigmoid(u W_beta)                            one a head
    S_t       = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t       = S_t^T q_t                                    S_{-1} = 0
    y         = W_o [RMSNorm_head(o_t) * sigmoid(u W_g)]

``conv``: causal, depthwise, ``short_conv_kernel_size`` taps, no bias
(``linear_silu``: silu after it); ``kda_safe_gate``: the log-decay a channel
lies in (``kda_lower_bound``, 0); ``group_norm_size`` 1: the norm is over a
head's ``d``.  The program's ``in_proj`` is ``[W_q | W_k | W_v | W_f | W_g |
W_beta]``.

**Latent attention**, ``d_n = qk_nope_head_dim``, ``d_r = qk_rope_head_dim =
rotary_dim``, ``d_v = v_head_dim``, ``q_lora_rank`` null::

    [q_n | q_r]  = u W_q                                     a head
    [c_kv | k_r] = u W_kva                                   kv_lora_rank | d_r
    [k_n | v]    = RMSNorm(c_kv) W_kvb                       a head
    q, k         = RMSNorm_q([q_n | q_r]), RMSNorm_k([k_n | k_r])   use_qk_norm
    scores       = ([q_n | rot(q_r)] . [k_n | rot(k_r)]) (d_n + d_r)^-1/2
    out          = (softmax_causal(scores) v * sigmoid(u W_gate)) W_o

``rot`` turns the pairs ``(x_i, x_{i + d_r / 2})`` by position times
``rope_theta ** (-2 i / d_r)``; the gate is one a head
(``gated_attention_proj_granularity_type`` ``head_wise``).

**Experts.**  ``s = sigmoid(u W_r)`` over all ``num_experts``; they are
``n_group`` groups one after another; a group's score is the sum of its two
largest ``s + b``; the ``topk_group`` best groups are kept; chosen = the
``num_experts_per_tok`` largest ``s + b`` among the kept groups' experts,
**by sorting**; ``w_e = routed_scaling_factor s_e / sum over chosen of s``;
out = ``sum over e chosen and held of w_e E_e(u) + E_shared(u)``, every ``E``
the gated form at ``moe_intermediate_size``; where a layer's limit ``c > 0``
(``expert_swiglu_limit_list``, ``share_expert_swiglu_limit_list``) the gate's
pre-activation is clipped above at ``c`` and the up-projection to ``[-c,
c]``.  The experts are a plain loop over the held ones, every token through
each, weighted by 0 where the token did not choose it.  What the absent
experts would add is left out here as in the program: the same share.

Weights arrive under the program's parameter names, because they are the
program's own seeded initial weights.  Nothing here imports the program.
Departures, none of which changes the arithmetic, so that one 8,192-token
sequence fits beside the program's state: rows of a batch go through
``lax.map``; a layer is made again for its backward pass and a layer's
input is made again from the embedding (``after_layers``), one layer at a
time; the scan over the positions keeps every ``STATE_EVERY``-th state and
makes the positions between again (8,192 x 32 heads x 64 KB of states would
be 16 GB a layer); the experts go one at a time, both mixers a group of
heads at a time (the attention a block of queries at a time too), the logits
a block of positions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.xing4 import as_is, made_again, rms, rot

MASKED = -1e30
QUERY_BLOCK = 256
HEAD_GROUP = 8
LOSS_BLOCK = 1024
STATE_EVERY = 64
L2_EPS = 1e-6

# How far the program may be from this reference (benchmarks/harness/
# probe.py and holdout.py say what is compared).  The program computes in
# bf16 (2^-8 = 3.9e-3 a rounding) on a bf16 stream, with float32 norms,
# gates, decays, running sums, solve and carried state, router and logits;
# the maps of a delta-rule mixer keep their float32 sums until q, k and v
# are rounded once for the rule.  Each limit is set from two readings on
# the v5e at the cell's full size (my chip runs, PR 39; PERF.md section 6):
# the largest the program gave over its seeds, and what this reference
# gives with its forward products in fp8 (benchmarks/tests/controls.py) in
# the program's place.
# - gradient, per leaf 0.25 over a floor of 0.01 of the whole gradient's
#   norm: the number that tells the precision of the probe.  The program's
#   worst leaf read 0.094 to 0.142 on 19 seeds, a layer's ``experts_down``
#   every time, with the embedding, the first layer's maps and its dense
#   block at 0.083 to 0.095 beside it: at these weights every leaf under
#   the last layers carries what bf16 does to the backward signal there.
#   This reference with its products in bf16 in the program's place reads
#   the same (0.076 and 0.091 on 2 seeds, a layer's ``experts_down``): it
#   is the stated precision's reading, not the program's own.  In fp8 it
#   reads 0.628 to 0.655 on 3 seeds (the embedding, the first layer's
#   maps).
# - loss 5e-4: the program 9e-7 to 1.6e-4 on 19 seeds; in bf16 the
#   reference reads 4.8e-6 and 6.2e-5, in fp8 1.2e-4 to 8.0e-4, across the
#   program's own.  At initial weights the logits are small and the loss
#   does not tell the precision; the limit holds a loss that is another
#   loss.
# - the evaluation's answer over the holdout's 4 x 4,096 labels, relative
#   loss 2.5e-4 and accuracy 1.5e-3.  The program read 1.4e-6 to 7.8e-5
#   and 0 to 6.1e-4 on 18 readings (17 seeds, rounds 2, 8 and 20).  The
#   reference in fp8 reads 5.4e-4 and 2.3e-3 in the loss (one seed, rounds
#   2 and 8: this limit tells the evaluation's precision; in bf16 it reads
#   6.5e-5 and 9.7e-6, as the program) and 4.9e-4 and 2.0e-3 in the
#   accuracy, across the program's own at round 2.  An answer of the stage
#   before reads 0.12 to 0.13 and 6.2e-3 to 6.2e-2; half the holdout 4.7e-4
#   to 1.8e-3 and 6.7e-4 to 1.7e-3: the loss sees both.
TOLERANCE = {"loss": 5e-4, "grad_leaf": 0.25, "grad_floor": 0.01,
             "eval_loss": 2.5e-4, "eval_acc": 1.5e-3}


def gated(u, w_gate, w_up, w_down, cast, limit: float = 0.0):
    gate, up = cast(u) @ cast(w_gate), cast(u) @ cast(w_up)
    if limit > 0:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return cast(jax.nn.silu(gate) * up) @ cast(w_down)


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, feed-forward) of every layer held."""
    return [("mla" if (model["first_layer"] + j + 1)
             % model["layer_group_size"] == 0 else "kda",
             "dense" if j < model["dense_layers"] else "moe")
            for j in range(model["depth"])]


# --- Kimi delta attention -----------------------------------------------------


def delta_rule(q, k, v, g, beta, cast=as_is):
    """``q``, ``k``, ``g``: (L, H, d); ``v``: (L, H, e); ``beta``: (L, H).
    The recurrence one position at a time; ``o``: (L, H, e)."""

    def position(state, this):
        q_t, k_t, v_t, g_t, beta_t = this
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("hk,hkv->hv", cast(k_t), cast(state))
        state = state + k_t[..., None] * (
            beta_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.einsum("hk,hkv->hv", cast(q_t), cast(state))

    @jax.checkpoint
    def some_positions(state, these):
        return lax.scan(position, state, these)

    length = q.shape[0]
    every = math.gcd(length, STATE_EVERY)
    _, out = lax.scan(
        some_positions,
        jnp.zeros((*k.shape[1:], v.shape[-1]), jnp.float32),
        tuple(a.reshape(length // every, every, *a.shape[1:])
              for a in (q, k, v, g, beta)))
    return out.reshape(length, *out.shape[2:])


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda(u, p, model: dict, cast=as_is):
    """``u``: (L, C), normed.  The heads go a group of ``HEAD_GROUP`` at a
    time, each with its own columns of the five maps and of the steps', its
    own taps and decays and its own rows of ``W_o``, whose products are
    summed."""
    length, width = u.shape
    heads, d = model["num_heads"], model["head_dim"]
    group = math.gcd(heads, HEAD_GROUP)
    groups, wide = heads // group, group * d
    inner, kernel = heads * d, p["in_proj"]
    taps = p["conv_kernel"]

    @jax.checkpoint
    def some_heads(total, weights):
        w_maps, w_beta, taps, dt_bias, a_log, w_o = weights
        # (L, 5, g d): q, k, v, the decay's, the output gate's.
        maps = jnp.einsum("lc,cmw->lmw", cast(u), cast(w_maps))
        padded = jnp.pad(maps[:, :3], ((taps.shape[0] - 1, 0), (0, 0), (0, 0)))
        q, k, v = (a.reshape(length, group, d) for a in jnp.moveaxis(
            jax.nn.silu(sum(padded[j:j + length] * taps[j]
                            for j in range(taps.shape[0]))), 1, 0))
        g = model["kda_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None]
            * (maps[:, 3] + dt_bias).reshape(length, group, d))
        o = delta_rule(unit(q) * d ** -0.5, unit(k), v, g,
                       jax.nn.sigmoid(cast(u) @ cast(w_beta)), cast)
        o = (rms(o, model["norm_eps"]) * p["norm"]).reshape(length, wide)
        return total + cast(o * jax.nn.sigmoid(maps[:, 4])) @ cast(w_o), None

    total, _ = lax.scan(some_heads, jnp.zeros_like(u), (
        jnp.moveaxis(kernel[:, :5 * inner].reshape(width, 5, groups, wide),
                     2, 0),
        jnp.moveaxis(kernel[:, 5 * inner:].reshape(width, groups, group),
                     1, 0),
        jnp.moveaxis(taps.reshape(taps.shape[0], 3, groups, wide), 2, 0),
        p["dt_bias"].reshape(groups, wide), p["A_log"].reshape(groups, group),
        p["out_proj"]["kernel"].reshape(groups, wide, width)))
    return total


# --- latent attention ---------------------------------------------------------


def mla(u, p, model: dict, cast=as_is):
    """``u``: (L, C), normed.  The heads go a group of ``HEAD_GROUP`` at a
    time, each with its own columns of ``W_q`` and ``W_kvb``, its own gates
    and its own rows of ``W_o``, whose products are summed."""
    length = u.shape[0]
    heads, d_n, d_r, d_v = (model["num_heads"], model["nope_dim"],
                            model["rope_dim"], model["v_dim"])
    rank, eps = model["kv_rank"], model["norm_eps"]
    kv_a = cast(u) @ cast(p["kv_a"]["kernel"])
    c_kv = rms(kv_a[:, :rank], eps) * p["kv_norm"]
    frequencies = model["rope_theta"] ** (
        -jnp.arange(0, d_r, 2, dtype=jnp.float32) / d_r)
    scale = (d_n + d_r) ** -0.5
    block = math.gcd(length, QUERY_BLOCK)
    group = math.gcd(heads, HEAD_GROUP)

    @jax.checkpoint
    def some_heads(total, weights):
        w_q, w_kv, w_gate, w_o = weights
        q = jnp.einsum("lc,chd->lhd", cast(u), cast(w_q))
        kv = jnp.einsum("lr,rhd->lhd", cast(c_kv), cast(w_kv))
        k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(
            kv_a[:, None, rank:], (length, group, d_r))], -1)
        q = rms(q, eps) * p["q_head_norm"]
        k = rms(k, eps) * p["k_head_norm"]
        q = jnp.concatenate(
            [q[..., :d_n], rot(q[..., d_n:], frequencies)], -1)
        k = jnp.concatenate(
            [k[..., :d_n], rot(k[..., d_n:], frequencies)], -1)
        v = kv[..., d_n:]

        @jax.checkpoint
        def some_queries(inp):
            start, qb = inp                            # (block, g, d)
            scores = scale * jnp.einsum("qhd,khd->hqk", cast(qb), cast(k))
            seen = (start + jnp.arange(block))[:, None] >= jnp.arange(length)
            weights = jax.nn.softmax(jnp.where(seen, scores, MASKED), axis=-1)
            return jnp.einsum("hqk,khe->qhe", cast(weights), cast(v))

        out = lax.map(some_queries, (
            jnp.arange(0, length, block),
            q.reshape(length // block, block, group, d_n + d_r)))
        out = out.reshape(length, group, d_v) * jax.nn.sigmoid(
            cast(u) @ cast(w_gate))[..., None]
        return total + jnp.einsum("lhe,hec->lc", cast(out), cast(w_o)), None

    def grouped(kernel, width):
        """A head group's columns, the groups in front."""
        return jnp.moveaxis(kernel.reshape(
            kernel.shape[0], heads // group, group, width), 1, 0)

    total, _ = lax.scan(some_heads, jnp.zeros_like(u), (
        grouped(p["q"]["kernel"], d_n + d_r),
        grouped(p["kv_b"]["kernel"], d_n + d_v),
        grouped(p["gate"]["kernel"], 1)[..., 0],
        p["out"]["kernel"].reshape(heads // group, group, d_v, -1)))
    return total


# --- feed-forward -------------------------------------------------------------


def descending(a):
    """The indices that sort the last axis from the largest down."""
    return jnp.argsort(-a, axis=-1)


def route(u, p, model: dict, cast=as_is):
    """The chosen experts (L, k) and their weights (L, k): the
    group-limited choice, by sorting."""
    scores = jax.nn.sigmoid(cast(u) @ cast(p["router"]))
    biased = scores + p["router_bias"]
    groups, kept = model["expert_groups"], model["expert_groups_kept"]
    if groups > 1:
        grouped = biased.reshape(biased.shape[0], groups, -1)
        group_scores = -jnp.sort(-grouped, axis=-1)[..., :2].sum(-1)
        best = descending(group_scores)[:, :kept]             # (L, kept)
        is_kept = jnp.zeros(group_scores.shape, bool).at[
            jnp.arange(biased.shape[0])[:, None], best].set(True)
        biased = jnp.where(is_kept[..., None], grouped, -jnp.inf).reshape(
            biased.shape)
    chosen = descending(biased)[:, :model["experts_per_token"]]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, model["routed_scale"] * picked / picked.sum(
        -1, keepdims=True)


def limit_of(model: dict, key: str, layer: int) -> float:
    limits = model.get(key) or ()
    return float(limits[layer]) if limits else 0.0


def moe(u, p, model: dict, layer: int, cast=as_is):
    chosen, weights = route(u, p, model, cast)
    limit = limit_of(model, "expert_limits", layer)

    @jax.checkpoint
    def one(total, expert):
        w_gate, w_up, w_down, e = expert
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return total + weight[:, None] * gated(
            u, w_gate, w_up, w_down, cast, limit), None

    held = p["experts_gate"].shape[0]
    total, _ = lax.scan(one, jnp.zeros_like(u), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        model["experts_first"] + jnp.arange(held)))
    return total + gated(
        u, p["shared_gate"], p["shared_up"], p["shared_down"], cast,
        limit_of(model, "shared_expert_limits", layer))


# --- the model ----------------------------------------------------------------


def layer(h, p, index: int, kinds: tuple[str, str], model: dict,
          cast=as_is):
    """One layer on the stream ``h`` (L, C)."""
    mixer = {"kda": kda, "mla": mla}[kinds[0]]
    eps = model["norm_eps"]

    @made_again
    def run(h, p):
        h = h + mixer(rms(h, eps) * p["mixer_norm"]["scale"], p["mixer"],
                      model, cast)
        u = rms(h, eps) * p["ffn_norm"]["scale"]
        if kinds[1] == "dense":
            return h + gated(u, p["ffn"]["gate"], p["ffn"]["up"],
                             p["ffn"]["down"], cast)
        return h + moe(u, p["ffn"], model, index, cast)

    return run(h, p)


def after_layers(h, layers, kinds, model: dict, cast=as_is):
    """The stream after the layers ``layers`` (their parameters).  A
    gradient keeps the stream that enters the first of them and no other:
    what enters layer ``k`` is made again from it for layer ``k``'s own
    backward pass."""
    if not layers:
        return h

    @made_again
    def run(h, layers):
        h = after_layers(h, layers[:-1], kinds[:-1], model, cast)
        return layer(h, layers[-1], len(layers) - 1, kinds[-1], model, cast)

    return run(h, layers)


def final_stream(params, ids_row, model: dict, cast=as_is):
    """One row of token ids (L,) through every layer: (L, C), normed."""
    kinds = layer_kinds(model)
    h = after_layers(
        params["embed"]["embedding"][ids_row],
        [params[f"layer_{j}"] for j in range(len(kinds))], kinds, model, cast)
    return rms(h, model["norm_eps"]) * params["norm"]["scale"]


def forward(params, ids, model: dict, cast=as_is):
    """Logits (B, L, vocabulary) for token ids (B, L); ``model`` is the
    configuration's ``experiment.model`` section.  ``cast`` is applied to
    both operands of every matrix product (the router's and the rule's
    included): the control of ``correct`` passes a rounding to the next
    precision below the configuration's; the reference itself leaves it
    out."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    return lax.map(
        lambda ids_row: cast(final_stream(params, ids_row, model, cast))
        @ cast(params["head"]["kernel"]), ids)


def loss(params, ids, y, model: dict):
    """Mean cross-entropy over every position; ``y`` (B, L), the token after
    each.  The logits of a block of positions at a time, and one
    ``jax.checkpoint`` around the whole of it: a gradient then keeps nothing
    of the forward pass while whatever else the caller computes runs beside
    it."""

    @jax.checkpoint
    def whole(params):
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        block = math.gcd(ids.shape[1], LOSS_BLOCK)

        @jax.checkpoint
        def some_positions(inp):
            h, labels = inp
            logp = jax.nn.log_softmax(h @ params["head"]["kernel"])
            return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()

        def row(inp):
            ids_row, y_row = inp
            h = final_stream(params, ids_row, model)
            return lax.map(some_positions, (
                h.reshape(-1, block, h.shape[-1]),
                y_row.reshape(-1, block))).sum()

        return lax.map(row, (ids, y)).sum() / y.size

    return whole(params)
