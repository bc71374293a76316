"""Plain reference for the ``evabyte`` family: forward pass and loss in
float32 ``jax.numpy``, written from the layer's equations.

The published model is EvaByte (``EvaByte/EvaByte`` ``config.json``: 4,096
wide, 32 heads of 128, feed-forward 11,008, vocabulary 320, 8 prediction
heads, ``window_size`` 2,048, ``chunk_size`` 16, rotary base 100,000, RMSNorm
eps 1e-5 with ``norm_add_unit_offset``, no biases).  One block, on a float32
residual stream ``h``::

    h <- h + Attn(RMSNorm(h)) ;  h <- h + W_down(silu(W_gate u) * W_up u),
    u = RMSNorm(h) ;  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)

Attention is EVA (Zheng et al., ICLR 2023, "Efficient Attention via Control
Variates") with input-independent pooling.  With ``s = 128 ** -0.5``, q and
k rotated by their positions, and per head two learned vectors ``mu``,
``phi``: every chunk ``c`` of 16 consecutive positions has one summary key
and one summary value,

    k~_c = sum_m softmax_m(s mu . k_m) k_m,  v~_c = sum_m softmax_m(s phi . k_m) v_m,

and query ``i`` in window ``w(i) = i // 2048`` takes one softmax over its
own window's keys up to itself and the summaries of every chunk of the
windows before ``w(i)``.  After the last block a final RMSNorm, then one
linear map to 8 x 320 logits: head ``j`` at position ``i`` predicts byte
``i + 1 + j``; the loss is the mean cross-entropy over positions and heads.

**Assumed, not read from the published config** (which fixes only the
sizes): the pooling form above, windows that do not overlap, the half-split
rotary form, the final norm.  They are as the model's public code is
recalled; the configuration file lists them under ``assumed``.

Weights arrive under the program's parameter names, because they are the
program's own seeded initial weights.  Nothing here imports the program.
So that one 16,384-byte sequence fits beside the program's state, rows go
through ``lax.map``, each block is a ``jax.checkpoint``, and inside a block
the attention runs over groups of heads and windows and the feed-forward
over windows of positions, one at a time: that changes no arithmetic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

RMS_NORM_EPS = 1e-5
MASKED = -1e30
HEAD_GROUP = 4

# How far the program may be from this reference (benchmarks/harness/
# probe.py and holdout.py say what is compared).  The program computes in
# bf16 (2^-8 = 3.9e-3 a rounding) on a float32 residual stream with float32
# logits.  Each limit is set from two readings on the v5e at the cell's
# full size (my chip runs, PR 28; PERF.md sections 2 and 6): the largest the
# program gave over its seeds, and what this reference gives with its
# forward products in fp8 (benchmarks/tests/controls.py): in the program's
# place on the probe's batch, and in its own place over the holdout.
# - gradient, per leaf 0.05 over a floor of 0.01 of the whole gradient's
#   norm: the number that tells the precision.  The program's worst leaf
#   read 0.0115 to 0.0135 on 20 seeds (a feed-forward gate or up projection each time);
#   the fp8 control 0.154 to 0.165 on 4.
# - loss 2e-4: the program 3.1e-6 to 4.3e-5 on 20 seeds, the control
#   4.8e-5 to 6.2e-4 on 4: across it, so the loss does not tell the
#   precision at initial weights (the logits start small); it holds a
#   loss that is another loss.
# - the evaluation's answer over the holdout's 4 x 16,384 x 8 labels,
#   relative loss 6e-5 and accuracy 5e-4.  The program read 5e-7 to 9.3e-6
#   and 0 to 1.07e-4 on 22 seeds at rounds 2, 10 and 12.  The reference in
#   fp8 reads 1.6e-4 to 3.2e-4 in the loss (this limit tells the
#   evaluation's precision too, which BERT's and the CNN's do not: half a
#   million labels at a loss of 5.3 leave the rounding nothing to cancel
#   against) and 2.1e-5 to 3.7e-4 in the accuracy, across the program's
#   own.  An answer of two rounds before reads 0.019 to 0.050 and 8.6e-4
#   to 0.011; half the holdout 2.3e-6 to 1.0e-3 in the loss: seen on 4 of
#   6 readings.
TOLERANCE = {"loss": 2e-4, "grad_leaf": 0.05, "grad_floor": 0.01,
             "eval_loss": 6e-5, "eval_acc": 5e-4}


def as_is(a):
    return a


def rms_norm(x, p):
    return x / jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + RMS_NORM_EPS
    ) * (1.0 + p["scale"])


def rotary(x, theta: float):
    """``x``: (L, H, D) at positions 0..L-1; coordinate ``i`` turns against
    ``i + D/2`` by ``position * theta ** (-2i/D)``."""
    length, _, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def chunk_summaries(k, v, mu, phi, chunk: int, cast):
    """``k``, ``v``: (H, L, D); ``mu``, ``phi``: (H, D).  One summary key
    and value per chunk: (H, L // chunk, D) each."""
    heads, length, dim = k.shape
    kc = k.reshape(heads, length // chunk, chunk, dim)
    vc = v.reshape(heads, length // chunk, chunk, dim)
    scale = dim ** -0.5
    by_mu = jax.nn.softmax(
        scale * jnp.einsum("hcmd,hd->hcm", cast(kc), cast(mu)), axis=-1)
    by_phi = jax.nn.softmax(
        scale * jnp.einsum("hcmd,hd->hcm", cast(kc), cast(phi)), axis=-1)
    return (jnp.einsum("hcm,hcmd->hcd", cast(by_mu), cast(kc)),
            jnp.einsum("hcm,hcmd->hcd", cast(by_phi), cast(vc)))


def eva(q, k, v, mu, phi, window: int, chunk: int, cast):
    """``q``, ``k``, ``v``: (H, L, D), rotated.  Every query against its own
    window's keys up to itself and every earlier window's chunk
    summaries, one softmax over both; a window at a time."""
    heads, length, dim = q.shape
    window = min(window, length)
    windows, per_window = length // window, window // chunk
    scale = dim ** -0.5
    ks, vs = chunk_summaries(k, v, mu, phi, chunk, cast)
    chunk_window = jnp.arange(length // chunk) // per_window
    causal = jnp.arange(window)[:, None] >= jnp.arange(window)[None, :]

    @jax.checkpoint
    def one_window(inp):
        w, qw, kw, vw = inp
        own = scale * jnp.einsum("hqd,hkd->hqk", cast(qw), cast(kw))
        own = jnp.where(causal, own, MASKED)
        far = scale * jnp.einsum("hqd,hcd->hqc", cast(qw), cast(ks))
        far = jnp.where(chunk_window < w, far, MASKED)
        weights = jax.nn.softmax(
            jnp.concatenate([own, far], axis=-1), axis=-1)
        return (jnp.einsum("hqk,hkd->hqd", cast(weights[..., :window]),
                           cast(vw))
                + jnp.einsum("hqc,hcd->hqd", cast(weights[..., window:]),
                             cast(vs)))

    def by_window(a):              # (H, L, D) -> (windows, H, window, D)
        return a.reshape(heads, windows, window, dim).transpose(1, 0, 2, 3)

    out = lax.map(one_window, (jnp.arange(windows), by_window(q),
                               by_window(k), by_window(v)))
    return out.transpose(1, 0, 2, 3).reshape(heads, length, dim)


def attention(u, p, model: dict, cast):
    """``u``: (L, width).  Projection kernels are (width, heads, head_dim),
    the output kernel (heads, head_dim, width).  Heads do not meet before
    the output projection sums over them, so a group of heads at a time."""
    heads = model["num_heads"]
    group = math.gcd(heads, HEAD_GROUP)

    def grouped(a, axis):
        a = jnp.moveaxis(a, axis, 0)
        return a.reshape(heads // group, group, *a.shape[1:])

    @jax.checkpoint
    def some_heads(weights):
        wq, wk, wv, wo, mu, phi = weights     # (G, width, D) ... (G, D)
        q, k, v = (jnp.einsum("ld,hde->hle", cast(u), cast(w))
                   for w in (wq, wk, wv))
        q, k = (rotary(a.transpose(1, 0, 2), model["rope_theta"]
                       ).transpose(1, 0, 2) for a in (q, k))
        out = eva(q, k, v, mu, phi, model["window_size"],
                  model["chunk_size"], cast)
        return jnp.einsum("hle,hed->ld", cast(out), cast(wo))

    total, _ = lax.scan(
        lambda total, weights: (total + some_heads(weights), None),
        jnp.zeros_like(u), (
            grouped(p["query"]["kernel"], 1), grouped(p["key"]["kernel"], 1),
            grouped(p["value"]["kernel"], 1), grouped(p["out"]["kernel"], 0),
            grouped(p["mu"], 0), grouped(p["phi"], 0)))
    return total


def feed_forward(u, p, model: dict, cast):
    """Position by position, so a window of positions at a time."""
    window = min(model["window_size"], u.shape[0])

    def some(rows):
        gate = cast(rows) @ cast(p["gate"]["kernel"])
        up = cast(rows) @ cast(p["up"]["kernel"])
        return cast(jax.nn.silu(gate) * up) @ cast(p["down"]["kernel"])

    return lax.map(jax.checkpoint(some),
                   u.reshape(-1, window, u.shape[-1])).reshape(u.shape)


def forward(params, ids, model: dict, cast=as_is):
    """Logits (B, L, heads of prediction, vocabulary) for byte ids (B, L);
    ``model`` is the configuration's ``experiment.model`` section.
    ``cast`` is applied to both operands of every matrix product: the
    control of ``correct`` passes a rounding to the next precision below
    the configuration's; the reference itself leaves it out."""
    if model.get("attn_impl", "flash") not in ("dense", "flash"):
        raise ValueError("the reference is EVA attention on one device")
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)

    @jax.checkpoint
    def block(h, p):
        h = h + attention(rms_norm(h, p["attn_norm"]), p, model, cast)
        return h + feed_forward(rms_norm(h, p["ffn_norm"]), p, model, cast)

    def row(ids_row):
        h = params["embed"]["embedding"][ids_row]
        for i in range(model["depth"]):
            h = block(h, params[f"block_{i}"])
        h = rms_norm(h, params["norm"])
        logits = cast(h) @ cast(params["head"]["kernel"])
        return logits.reshape(
            len(ids_row), model["num_pred_heads"], model["vocab_size"])

    return lax.map(row, ids)


def loss(params, ids, y, model: dict):
    """Mean cross-entropy over every position and prediction head; ``y``
    (B, L, heads of prediction).  One ``jax.checkpoint`` around the whole
    of it: a gradient then keeps nothing of the forward pass while
    whatever else the caller computes runs beside it."""

    @jax.checkpoint
    def whole(params):
        logp = jax.nn.log_softmax(forward(params, ids, model))
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()

    return whole(params)
