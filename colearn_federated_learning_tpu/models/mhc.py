"""A residual path of several streams mixed by doubly stochastic maps
(manifold-constrained hyper-connections, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606).

The stream of a token is ``X_t`` of ``n`` rows of width ``C``.  A sublayer
``F`` reads one mixture of the rows, writes back into all of them and mixes
them among themselves, each by a map computed from the token's own stream::

    x~      = RMSNorm_w(flatten(X_t))                  over all n C, float32
    H~pre   = a_pre  (x~ Phi_pre)  + b_pre             (n,)
    H~post  = a_post (x~ Phi_post) + b_post            (n,)
    H~res   = a_res mat(x~ Phi_res) + b_res            (n, n)
    H_pre   = sigmoid(H~pre) ;  H_post = 2 sigmoid(H~post)
    M_0     = exp(clip(H~res, clamp_min, clamp_max))
    M_k     = columns(rows(M_{k-1})),  rows(M) = M / (M 1 + eps),
              columns(M) = M / (1^T M + eps) ;  H_res = M_iters
    u       = sum_j H_pre[j] X_t[j]
    X_t[i] <- sum_j H_res[i, j] X_t[j] + H_post[i] F(RMSNorm(u))

and there is no other residual.  ``Phi = [Phi_pre | Phi_post | Phi_res]``
is one matrix ``(n C, 2 n + n^2)``, ``b`` one vector beside it, ``a`` the
three scalars.

**Layout.**  The stream is held ``(B, n, L, C)``, the rows in front of the
positions: with the rows next to the width, the chip would pad ``n = 4``
to a tile's 16 sublanes.  The maps are held with the positions last,
``(B, n, n, L)`` and ``(B, n, L)``, float32: as ``(L, n, n)`` every one of
the Sinkhorn iterations' intermediates, kept for the backward pass, would
pad 16 numbers to an (8, 128) tile a token.  Sums over the rows are written
as sums of slices, which XLA fuses with what is around them; the
iterations are unrolled and differentiated as written.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from colearn_federated_learning_tpu import telemetry

GATE_INIT = 0.01
# ``H_res`` starts near the identity: before the iterations its diagonal
# stands e^RES_DIAGONAL above the rest.
RES_DIAGONAL = 4.0


def _total(a, axis: int):
    """Sum over a short axis as a sum of slices."""
    return sum(lax.index_in_dim(a, i, axis, keepdims=True)
               for i in range(a.shape[axis]))


def float32_dot(x, w):
    """``x @ w`` to float32's precision, ``w`` (C, m) float32 and narrow.
    A bfloat16 ``x`` is exact as it is, so ``w`` goes in as three bfloat16
    parts that sum to it, side by side in one product with float32
    accumulation: the chip's matrix unit has 128 columns and ``3 m`` fill
    fewer, where a float32 product would make six passes over a float32
    copy of ``x``."""
    if x.dtype != jnp.bfloat16:
        return jnp.dot(x.astype(jnp.float32), w,
                       precision=lax.Precision.HIGHEST)
    parts, rest = [], w
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    out = jnp.dot(x, jnp.concatenate(parts, axis=-1),
                  preferred_element_type=jnp.float32)
    return sum(jnp.split(out, 3, axis=-1))


def sinkhorn(m, iters: int, eps: float):
    """``m``: (..., n, n, L) positive.  ``iters`` times: every row divided
    by its sum plus ``eps``, then every column by its."""
    for _ in range(iters):
        m = m / (_total(m, -2) + eps)           # row i: over j
        m = m / (_total(m, -3) + eps)           # column j: over i
    return m


def read_stream(x, pre):
    """``sum_j pre[j] X[j]``: ``x`` (B, n, L, C), ``pre`` (B, n, L); float32
    (B, L, C)."""
    return sum(pre[:, j, :, None] * x[:, j].astype(jnp.float32)
               for j in range(x.shape[1]))


def write_stream(x, res, post, out):
    """``X[i] <- sum_j res[i, j] X[j] + post[i] out``: ``res`` (B, n, n,
    L), ``post`` (B, n, L), ``out`` (B, L, C); in float32, rounded to the
    stream's precision once."""
    n = x.shape[1]
    rows = [x[:, j].astype(jnp.float32) for j in range(n)]
    out = out.astype(jnp.float32)
    return jnp.stack([
        sum(res[:, i, j, :, None] * rows[j] for j in range(n))
        + post[:, i, :, None] * out for i in range(n)], axis=1
    ).astype(x.dtype)


def _bias_init(n: int):
    """``H_pre`` starts at ``1 / n`` a row (they sum to one), ``H_post`` at
    1, ``H_res`` near the identity."""
    def init(key, shape, dtype=jnp.float32):
        del key
        pre = jnp.full((n,), -jnp.log(n - 1.0) if n > 1 else 30.0)
        return jnp.concatenate([
            pre, jnp.zeros((n,)),
            (RES_DIAGONAL * jnp.eye(n)).reshape(-1)]).astype(dtype)
    return init


class StreamMaps(nn.Module):
    """The three maps of one sublayer, from the stream it reads."""

    sinkhorn_iters: int = 20
    eps: float = 1e-6                   # the iterations' (``hc_eps``)
    clamp: tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6
    init_std: float = 0.02

    @nn.compact
    def __call__(self, x):
        """``x``: (B, n, L, C).  Returns ``H_pre`` (B, n, L), ``H_post``
        (B, n, L) and ``H_res`` (B, n, n, L), float32."""
        B, n, L, C = x.shape
        registry = telemetry.get_registry()     # set on every build
        registry.gauge("mhc.streams").set(n)
        registry.gauge("mhc.sinkhorn_iters").set(self.sinkhorn_iters)
        scale = self.param("norm", nn.initializers.ones, (n * C,))
        phi = self.param("phi", nn.initializers.normal(self.init_std),
                         (n * C, 2 * n + n * n))
        bias = self.param("bias", _bias_init(n), (2 * n + n * n,))
        gates = self.param(
            "gates", nn.initializers.constant(GATE_INIT), (3,))
        # RMSNorm_w(x) Phi = rsqrt(mean x^2 + eps) * (x (w . Phi)): the
        # stream is read as it lies, a row at a time, never flattened and
        # never held in float32.
        mean_sq = sum(jnp.mean(jnp.square(x[:, j].astype(jnp.float32)),
                               axis=-1) for j in range(n)) / n
        folded = (scale[:, None] * phi).reshape(n, C, -1)
        raw = sum(float32_dot(x[:, j], folded[j]) for j in range(n))
        raw = raw * lax.rsqrt(mean_sq[..., None] + self.norm_eps)  # (B, L, m)
        raw = jnp.swapaxes(raw, 1, 2)                        # (B, m, L)
        gate = jnp.repeat(gates, jnp.array([n, n, n * n]),
                          total_repeat_length=2 * n + n * n)
        raw = gate[:, None] * raw + bias[:, None]
        pre = nn.sigmoid(raw[:, :n])
        post = 2.0 * nn.sigmoid(raw[:, n:2 * n])
        res = jnp.exp(jnp.clip(raw[:, 2 * n:], *self.clamp)).reshape(
            B, n, n, L)
        return pre, post, sinkhorn(res, self.sinkhorn_iters, self.eps)
