"""Plain reference for the ``xing4`` family: forward pass and loss in
float32 ``jax.numpy``, written from the layers' equations.

The published model is ``XingChen-AGI/Xing4.0-29B-A4B`` (``config.json``,
``model_type`` ``xing4_0``).  No modelling code is on the machine: the
equations below are what this file implements, each name on the left a key
of the published config, and the configuration file lists under
``assumed`` every choice the keys do not fix.  Attention, yarn, the router
and the prediction module are DeepSeek-V2/V3's published forms
(arXiv:2405.04434, arXiv:2412.19437), whose key names the config carries;
the residual path is manifold-constrained hyper-connections
(arXiv:2512.24880, on hyper-connections, arXiv:2409.19606), as recalled.

**The residual path.**  A token's stream is ``X_t`` of ``n = hc_mult``
rows of ``C = hidden_size``; ``X_0[t, j] = Emb(id_t)`` for every ``j``.  A
layer is two sublayers, ``F`` = attention then ``F`` = feed-forward, each
with its own maps::

    x~     = RMSNorm_w(flatten(X_t))               over n C; eps rms_norm_eps
    H~pre  = a_pre  (x~ Phi_pre)  + b_pre          (n,)
    H~post = a_post (x~ Phi_post) + b_post         (n,)
    H~res  = a_res mat(x~ Phi_res) + b_res         (n, n); a_* scalars
    H_pre  = sigmoid(H~pre) ;  H_post = 2 sigmoid(H~post)
    M_0    = exp(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M_k    = columns(rows(M_{k-1})) ;  rows(M) = M / (M 1 + hc_eps),
             columns(M) = M / (1^T M + hc_eps) ;  k = 1 .. hc_sinkhorn_iters
    H_res  = M_last
    u      = sum_j H_pre[j] X_t[j]
    X_t[i] <- sum_j H_res[i, j] X_t[j] + H_post[i] F(RMSNorm(u))

and there is no other residual.  After the last layer ``h_t = sum_j
X_t[j]``, a final RMSNorm, the untied head.  ``Phi = [Phi_pre | Phi_post |
Phi_res]`` is the parameter ``phi`` (n C, 2 n + n^2), ``b`` ``bias``, ``a``
``gates``.

**Attention**, heads ``H``, ``d_n = qk_nope_head_dim``, ``d_r =
qk_rope_head_dim``, ``d_v = v_head_dim``, no biases::

    c_q          = RMSNorm(u W_qa)                      q_lora_rank
    [q_n | q_r]  = c_q W_qb                             a head
    [c_kv | k_r] = u W_kva                              kv_lora_rank | d_r
    [k_n | v]    = RMSNorm(c_kv) W_kvb                  a head
    scores       = ([q_n | rot(q_r)] . [k_n | rot(k_r)])
                   (d_n + d_r)^-1/2 (0.1 mscale_all_dim ln factor + 1)^2
    out          = softmax_causal(scores) v W_o

``k_r`` is one head for all; ``rot`` turns the pairs ``(x_i, x_{i + d_r /
2})`` by position times yarn's frequencies (``rope_theta``, ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``); cos
and sin are unscaled because ``mscale`` equals ``mscale_all_dim``.  The
uncompressed training form: no cache, no absorbed product.

**Feed-forward.**  Layers before ``first_k_dense_replace``: ``W_d (silu(W_g
u) * W_u u)`` at ``intermediate_size``.  The others: ``s = sigmoid(u
W_r)`` over all ``n_routed_experts``; chosen = the ``num_experts_per_tok``
largest of ``s + b`` (one group: no group limits); ``w_e =
routed_scaling_factor s_e / sum over chosen of s``; out = ``sum over e
chosen and held of w_e E_e(u) + E_shared(u)``, every ``E`` the gated form
at ``moe_intermediate_size``.  The experts are a plain loop over the held
ones, every token through each, weighted by 0 where the token did not
choose it.  What the absent experts would add is left out here as in the
program: the same share.

**The prediction module** (``num_nextn_predict_layers`` 1): ``h'_t =
[RMSNorm(h_t) ; RMSNorm(Emb(id_{t+1}))] W_eh``, one whole layer with
experts on ``n`` copies of ``h'``, the rows summed, its own final RMSNorm,
the model's embedding and head; its logits at ``t`` predict ``id_{t+2}``.
The token after a row's last is taken to be id 0.  The logits are (B, L, 1
+ modules, vocabulary); the loss is the mean over positions and heads.

Weights arrive under the program's parameter names, because they are the
program's own seeded initial weights.  Nothing here imports the program.
So that one 8,192-token sequence fits beside the program's state, the
stream is held with its rows in front of the positions and the maps with
the positions last (the chip pads a short second-to-last dimension to a
tile), rows of a batch go through ``lax.map``, each sublayer and layer is
made again for its backward pass (``made_again``) and a layer's input is
made again from the embedding (``after_layers``), the experts go one at a
time, the
attention a group of heads and a block of queries at a time and the logits a block of
positions at a time: that changes no arithmetic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MASKED = -1e30
QUERY_BLOCK = 256
HEAD_GROUP = 8
LOSS_BLOCK = 1024

# How far the program may be from this reference (benchmarks/harness/
# probe.py and holdout.py say what is compared).  The program computes in
# bf16 (2^-8 = 3.9e-3 a rounding) on a bf16 stream of four rows, with
# float32 norms, maps, Sinkhorn iterations, router and logits.  Each limit
# is set from two readings on the v5e at the cell's full size (my chip
# runs, PR 35; PERF.md section 7): the largest the program gave over its
# seeds, and what this reference gives with its forward products in fp8
# (benchmarks/tests/controls.py) in the program's place.
# - gradient, per leaf 0.07 over a floor of 0.01 of the whole gradient's
#   norm: the number that tells the precision of the probe.  The program's
#   worst leaf read 0.0358 to 0.0455 on 7 seeds, a layer's
#   ``experts_down`` every time (what flows into it are the rows of the
#   routed experts, and the router's choice of 4 of 64 is where the two
#   sides can differ by more than rounding: bf16 in the stream moves a
#   score across the fourth place, for the least-weighted pairs).  The fp8
#   control of the probe does not fit the chip (two passes of this
#   reference in one program: 17.14 GiB of 15.75); at the CPU tests' size
#   it reads 0.144 where the program reads 0.047.
# - loss 3e-4: the program 1.6e-6 to 7.4e-5 on 7 seeds.  At initial
#   weights the logits are small and the loss does not tell the precision
#   (the control reads 1.2e-5 at the tests' size); the limit holds a loss
#   that is another loss.
# - the evaluation's answer over the holdout's 4 x 8,192 labels, relative
#   loss 4e-4 and accuracy 1.5e-3.  The program read 6.0e-6 to 1.07e-4 and
#   6.1e-5 to 5.5e-4 on 10 readings (8 seeds, rounds 2, 14 and 16).  The
#   reference in fp8 reads 2.9e-3 and 7.4e-3 in the loss (one seed, rounds
#   2 and 16: this limit tells the evaluation's precision; in bf16 it reads
#   1.9e-6 and 5.3e-5, as the program) and 1.0e-3 and 4.6e-4 in the
#   accuracy, across the program's own.  An answer of the stage before
#   reads 0.28 to 0.61 and 0.10 to 0.17; half the holdout 3.1e-3 to 3.2e-3
#   and 1.3e-3 to 6.8e-3: the loss sees both.
TOLERANCE = {"loss": 3e-4, "grad_leaf": 0.07, "grad_floor": 0.01,
             "eval_loss": 4e-4, "eval_acc": 1.5e-3}


def as_is(a):
    return a


def made_again(f):
    """``f(x, p)`` whose backward pass keeps ``x`` and ``p`` alone and makes
    the forward pass again, as ``jax.checkpoint`` does, but not before the
    gradient of its result has arrived: the chip's compiler is otherwise
    free to make the streams of several layers again at once, each 470 MB
    at the cell's size."""

    @jax.custom_vjp
    def run(x, p):
        return f(x, p)

    def forward(x, p):
        return f(x, p), (x, p)

    def backward(kept, g):
        x, p = kept
        x, g = lax.optimization_barrier((x, g))
        return jax.vjp(f, x, p)[1](g)

    run.defvjp(forward, backward)
    return run


def rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def gated(u, w_gate, w_up, w_down, cast):
    hidden = jax.nn.silu(cast(u) @ cast(w_gate)) * (cast(u) @ cast(w_up))
    return cast(hidden) @ cast(w_down)


# --- the residual path --------------------------------------------------------


def stream_maps(x, p, model: dict, cast=as_is):
    """``x``: (n, L, C).  ``H_pre`` (n, L), ``H_post`` (n, L), ``H_res``
    (n, n, L), the position last."""
    n, length, width = x.shape
    eps = model["norm_eps"]
    # RMSNorm_w(x) Phi with the norm's weight taken into Phi's rows and
    # its root divided out after the product: the normed stream, as large
    # as the stream, is never held.
    weighted = p["norm"][:, None] * p["phi"]
    raw = jnp.einsum("jlc,jcm->ml", cast(x),
                     cast(weighted.reshape(n, width, -1)))
    raw = raw / jnp.sqrt(jnp.mean(x * x, axis=(0, 2)) + eps)
    a_pre, a_post, a_res = p["gates"]
    bias = p["bias"]
    pre = jax.nn.sigmoid(a_pre * raw[:n] + bias[:n, None])
    post = 2.0 * jax.nn.sigmoid(a_post * raw[n:2 * n] + bias[n:2 * n, None])
    scores = (a_res * raw[2 * n:] + bias[2 * n:, None]).reshape(n, n, length)
    m = jnp.exp(jnp.clip(scores, model["res_clamp_min"],
                         model["res_clamp_max"]))
    for _ in range(model["sinkhorn_iters"]):
        m = m / (m.sum(axis=1, keepdims=True) + model["sinkhorn_eps"])
        m = m / (m.sum(axis=0, keepdims=True) + model["sinkhorn_eps"])
    return pre, post, m


def sublayer(x, p, name: str, f, model: dict, cast=as_is):
    """``X[i] <- sum_j H_res[i, j] X[j] + H_post[i] F(RMSNorm(u))``."""

    @made_again
    def run(x, p):
        pre, post, res = stream_maps(x, p[f"{name}_maps"], model, cast)
        u = jnp.sum(pre[:, :, None] * x, axis=0)
        out = f(rms(u, model["norm_eps"]) * p[f"{name}_norm"]["scale"],
                p[name])
        return (jnp.sum(res[:, :, :, None] * x[None], axis=1)
                + post[:, :, None] * out[None])

    return run(x, p)


# --- attention ----------------------------------------------------------------


def yarn_frequencies(model: dict) -> np.ndarray:
    dim, theta = model["rope_dim"], model["rope_theta"]
    factor = model["yarn_factor"]
    plain = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    if factor == 1:
        return plain.astype(np.float32)

    def correction(turns):
        return dim * math.log(model["yarn_original_max"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(model["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(model["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (plain / factor * (1 - keep) + plain * keep).astype(np.float32)


def rot(x, frequencies):
    """``x``: (L, ..., d): the pairs ``(x_i, x_{i + d / 2})`` turned by the
    position times the frequency."""
    half = x.shape[-1] // 2
    angles = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
              * jnp.asarray(frequencies)[None, :])
    angles = angles.reshape(x.shape[0], *(1,) * (x.ndim - 2), half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angles) - b * jnp.sin(angles),
                            b * jnp.cos(angles) + a * jnp.sin(angles)], -1)


def attention(u, p, model: dict, cast=as_is):
    """``u``: (L, C), normed.  The heads go a group of ``HEAD_GROUP`` at a
    time, each with its own columns of ``W_qb`` and ``W_kvb`` and its own
    rows of ``W_o``, whose products are summed."""
    length = u.shape[0]
    heads, d_n, d_r, d_v = (model["num_heads"], model["nope_dim"],
                            model["rope_dim"], model["v_dim"])
    rank, eps = model["kv_rank"], model["norm_eps"]
    c_q = rms(cast(u) @ cast(p["q_a"]["kernel"]), eps) * p["q_norm"]
    kv_a = cast(u) @ cast(p["kv_a"]["kernel"])
    c_kv = rms(kv_a[:, :rank], eps) * p["kv_norm"]
    frequencies = yarn_frequencies(model)
    k_r = rot(kv_a[:, rank:], frequencies)
    scale = (d_n + d_r) ** -0.5
    if model["yarn_factor"] > 1:
        scale *= (0.1 * model["yarn_mscale_all_dim"]
                  * math.log(model["yarn_factor"]) + 1.0) ** 2
    block = math.gcd(length, QUERY_BLOCK)
    group = math.gcd(heads, HEAD_GROUP)

    @jax.checkpoint
    def some_heads(total, weights):
        w_q, w_kv, w_o = weights       # (r_q, g, d_qk), (r_kv, g, ..), (g, d_v, C)
        q = jnp.einsum("lr,rhd->lhd", cast(c_q), cast(w_q))
        kv = jnp.einsum("lr,rhd->lhd", cast(c_kv), cast(w_kv))
        q = jnp.concatenate(
            [q[..., :d_n], rot(q[..., d_n:], frequencies)], -1)
        k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(
            k_r[:, None, :], (length, group, d_r))], -1)
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
        out = out.reshape(length, group, d_v)
        return total + jnp.einsum("lhe,hec->lc", cast(out), cast(w_o)), None

    def grouped(kernel, rows_in, width):
        """A head group's columns, the groups in front."""
        return jnp.moveaxis(kernel.reshape(
            rows_in, heads // group, group, width), 1, 0)

    total, _ = lax.scan(some_heads, jnp.zeros_like(u), (
        grouped(p["q_b"]["kernel"], model["q_rank"], d_n + d_r),
        grouped(p["kv_b"]["kernel"], rank, d_n + d_v),
        p["out"]["kernel"].reshape(heads // group, group, d_v, -1)))
    return total


# --- feed-forward -------------------------------------------------------------


def route(u, p, model: dict, cast=as_is):
    """The chosen experts (L, k) and their weights (L, k)."""
    scores = jax.nn.sigmoid(cast(u) @ cast(p["router"]))
    _, chosen = lax.top_k(scores + p["router_bias"],
                          model["experts_per_token"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, model["routed_scale"] * picked / picked.sum(
        -1, keepdims=True)


def routed_part(u, p, model: dict, cast=as_is):
    """``sum over e chosen and held of w_e E_e(u)``: (L, C)."""
    chosen, weights = route(u, p, model, cast)

    @jax.checkpoint
    def one(total, expert):
        w_gate, w_up, w_down, e = expert
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return total + weight[:, None] * gated(
            u, w_gate, w_up, w_down, cast), None

    held = p["experts_gate"].shape[0]
    total, _ = lax.scan(one, jnp.zeros_like(u), (
        p["experts_gate"], p["experts_up"], p["experts_down"],
        model["experts_first"] + jnp.arange(held)))
    return total


def moe(u, p, model: dict, cast=as_is):
    return routed_part(u, p, model, cast) + gated(
        u, p["shared_gate"], p["shared_up"], p["shared_down"], cast)


def dense_ffn(u, p, model: dict, cast=as_is):
    return gated(u, p["gate"], p["up"], p["down"], cast)


FEED_FORWARD = {"dense": dense_ffn, "moe": moe}


# --- the model ----------------------------------------------------------------


def layer(x, p, kind: str, model: dict, cast=as_is):
    """One layer on the stream ``x`` (n, L, C)."""

    @made_again
    def run(x, p):
        x = sublayer(x, p, "attn",
                     lambda u, q: attention(u, q, model, cast), model, cast)
        return sublayer(
            x, p, "ffn",
            lambda u, q: FEED_FORWARD[kind](u, q, model, cast), model, cast)

    return run(x, p)


def copies(h, model: dict):
    return jnp.broadcast_to(h[None], (model["hc_streams"], *h.shape))


def after_layers(x, layers, kinds, model: dict, cast=as_is):
    """The stream ``x`` after the layers ``layers`` (their parameters) of
    the kinds ``kinds``.  A gradient keeps the stream that enters the first
    of them and no other: what enters layer ``k`` is made again from it
    for layer ``k``'s own backward pass (a float32 stream of four rows is
    470 MB at the cell's size, and the probe that holds this reference
    beside the program has room for few)."""
    if not layers:
        return x

    @made_again
    def run(x, layers):
        x = after_layers(x, layers[:-1], kinds[:-1], model, cast)
        return layer(x, layers[-1], kinds[-1], model, cast)

    return run(x, layers)


def final_streams(params, ids_row, model: dict, cast=as_is):
    """One row of token ids (L,) through every layer: the summed rows
    before each head's final norm, a list of (L, C), the model's own first
    and the prediction module's after it."""
    kinds = ["dense" if i < model["dense_layers"] else "moe"
             for i in range(model["depth"])]
    x = copies(params["embed"]["embedding"][ids_row], model)
    h = jnp.sum(after_layers(
        x, [params[f"layer_{i}"] for i in range(len(kinds))], kinds, model,
        cast), axis=0)
    if not model["mtp_modules"]:
        return [h]

    @jax.checkpoint
    def module(h, params):
        eps = model["norm_eps"]
        ahead = jnp.concatenate([ids_row[1:], jnp.zeros((1,), ids_row.dtype)])
        joined = jnp.concatenate([
            rms(h, eps) * params["mtp_h_norm"]["scale"],
            rms(params["embed"]["embedding"][ahead], eps)
            * params["mtp_e_norm"]["scale"]], axis=-1)
        start = cast(joined) @ cast(params["mtp_proj"]["kernel"])
        return jnp.sum(layer(copies(start, model), params["mtp_layer"],
                             "moe", model, cast), axis=0)

    return [h, module(h, params)]


def head_norms(params, model: dict):
    return [params["norm"]["scale"]] + (
        [params["mtp_norm"]["scale"]] if model["mtp_modules"] else [])


def forward(params, ids, model: dict, cast=as_is):
    """Logits (B, L, 1 + modules, vocabulary) for token ids (B, L);
    ``model`` is the configuration's ``experiment.model`` section.  ``cast``
    is applied to both operands of every matrix product (the router's and
    the maps' included): the control of ``correct`` passes a rounding to
    the next precision below the configuration's; the reference itself
    leaves it out."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)

    def row(ids_row):
        return jnp.stack([
            cast(rms(h, model["norm_eps"]) * scale)
            @ cast(params["head"]["kernel"])
            for h, scale in zip(final_streams(params, ids_row, model, cast),
                                head_norms(params, model))])

    # (B, heads, L, V) turned: two heads next to the vocabulary would be
    # padded to a tile's eight.
    return jnp.swapaxes(lax.map(row, ids), 1, 2)


def loss(params, ids, y, model: dict):
    """Mean cross-entropy over every position and head; ``y`` (B, L, 1 +
    modules), the tokens after each position.  The logits of a block of
    positions at a time, and one ``jax.checkpoint`` around the whole of it:
    a gradient then keeps nothing of the forward pass while whatever else
    the caller computes runs beside it."""

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
            total = 0.0
            for j, (h, scale) in enumerate(zip(
                    final_streams(params, ids_row, model),
                    head_norms(params, model))):
                h = rms(h, model["norm_eps"]) * scale
                total += lax.map(some_positions, (
                    h.reshape(-1, block, h.shape[-1]),
                    y_row[:, j].reshape(-1, block))).sum()
            return total

        return lax.map(row, (ids, y)).sum() / y.size

    return whole(params)
