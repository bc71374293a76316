"""Small conv net — BASELINE config #2 ("FedAvg CNN on CIFAR-10").

Parity target: the reference's CNN-scale PyTorch module (SURVEY.md §2
"Models"; source unavailable — see SURVEY.md banner).  Design is TPU-first:
NHWC layout, bfloat16 compute, GroupNorm instead of BatchNorm — batch
statistics are a poor fit for federated local training (tiny per-client
batches, stats that would otherwise need cross-client sync) and GroupNorm
keeps the whole local round a pure function of (params, batch).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype
from jax import lax

from colearn_federated_learning_tpu import telemetry

# The TPU's tile is 128 lanes wide and channels are the lane axis: an
# activation of 64 channels is stored, read and written padded to 128.
LANES = 128
GROUP_NORM_EPS = 1e-6


def space_to_depth(x: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """Fold ``block x block`` spatial patches into channels:
    (N, H, W, C) -> (N, H/b, W/b, C·b²).  MFU lever for the stem conv —
    CIFAR's 3 input channels waste the MXU's 128-lane contraction dim,
    while 12 channels over 4x fewer positions tile it 4x better with the
    same receptive-field economics (PERF.md §5b)."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // block, block, w // block, block, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        n, h // block, w // block, c * block * block
    )


def fold_columns(x: jnp.ndarray) -> jnp.ndarray:
    """Two adjacent columns into the channel axis: (N, H, W, C) ->
    (N, H, W/2, 2C); folded channel ``s * C + c`` is channel ``c`` of
    column ``2j + s``.  Row-major, a view."""
    n, h, w, c = x.shape
    return x.reshape(n, h, w // 2, 2 * c)


def fold_kernel(kernel: jnp.ndarray) -> jnp.ndarray:
    """The 3x3 kernel (3, 3, C_in, C_out) as the (3, 3, 2 C_in, 2 C_out)
    kernel that computes the same same-padded convolution on
    ``fold_columns``' view.  Output column ``2j + s_out`` reads input
    column ``2j + s_out + kw - 1``, which is sub-column ``s_in`` of folded
    column ``j + t - 1``: block (t, s_in, s_out) is tap
    ``kw = 2 (t - 1) + s_in - s_out + 1`` where that is one, and zeros."""
    zero = jnp.zeros_like(kernel[:, 0])

    def block(t, s_in, s_out):
        kw = 2 * (t - 1) + s_in - s_out + 1
        return kernel[:, kw] if 0 <= kw <= 2 else zero

    return jnp.stack(
        [jnp.block([[block(t, s_in, s_out) for s_out in (0, 1)]
                    for s_in in (0, 1)]) for t in range(3)], axis=1)


class FoldedConv(nn.Module):
    """``nn.Conv(features, (3, 3), padding="SAME")`` on ``fold_columns``'
    view, with ``nn.Conv``'s parameters (same names, shapes, initialisers):
    the folded kernel is assembled from them inside the step, so autodiff
    folds the weight gradient back."""
    features: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (3, 3, x.shape[-1] // 2, self.features))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = lax.conv_general_dilated(
            x, fold_kernel(kernel), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + jnp.tile(bias, 2)


class FoldedGroupNorm(nn.Module):
    """``nn.GroupNorm(num_groups)`` on ``fold_columns``' view, with its
    parameters and its arithmetic (float32 sums, ``E[x^2] - E[x]^2``, one
    cast at the end): the sums over positions are taken per lane, and the
    two sub-columns and a group's channels are folded on that (N, 2C)."""
    num_groups: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        n, h, w2, c2 = x.shape
        ch = c2 // 2
        scale = self.param("scale", nn.initializers.ones, (ch,))
        bias = self.param("bias", nn.initializers.zeros, (ch,))
        size = ch // self.num_groups

        def per_group(a):                 # (N, 2C) -> (N, groups)
            return a.reshape(n, 2, self.num_groups, size).sum(axis=(1, 3))

        def per_lane(a):                  # (N, groups) -> (N, 1, 1, 2C)
            return jnp.tile(jnp.repeat(a, size, axis=-1), (1, 2))[
                :, None, None, :]

        xf = x.astype(jnp.promote_types(self.dtype, jnp.float32))
        count = h * w2 * 2 * size
        mean = per_group(xf.sum(axis=(1, 2))) / count
        mean2 = per_group((xf * xf).sum(axis=(1, 2))) / count
        var = jnp.maximum(0.0, mean2 - mean * mean)
        mul = per_lane(lax.rsqrt(var + GROUP_NORM_EPS)) * jnp.tile(scale, 2)
        y = (x - per_lane(mean)) * mul + jnp.tile(bias, 2)
        return y.astype(self.dtype)


def _row_pool(x):
    return nn.max_pool(x, (2, 1), strides=(2, 1))


@jax.custom_vjp
def folded_max_pool(x: jnp.ndarray) -> jnp.ndarray:
    """``nn.max_pool(x, (2, 2), strides=(2, 2))`` of the (N, H, W, C) array
    that ``x`` is ``fold_columns``' view of: the larger of row pairs, on
    full lanes, then of the two lane halves.  The gradient goes to one
    largest element of a window, as ``nn.max_pool``'s does; it is written
    out because autodiff's keeps two masks of the 64-lane result and
    takes three passes over them (PERF.md section 6, PR 30)."""
    return _folded_max_pool_fwd(x)[0]


def _folded_max_pool_fwd(x):
    c = x.shape[-1] // 2
    rows = _row_pool(x)
    return jnp.maximum(rows[..., :c], rows[..., c:]), (x, rows)


def _folded_max_pool_bwd(residuals, g):
    x, rows = residuals
    c = x.shape[-1] // 2
    left = jnp.where(rows[..., :c] >= rows[..., c:], g, jnp.zeros_like(g))
    _, row_vjp = jax.vjp(_row_pool, x)
    return row_vjp(jnp.concatenate([left, g - left], axis=-1))


folded_max_pool.defvjp(_folded_max_pool_fwd, _folded_max_pool_bwd)


def _conv3x3(features, dtype, name):
    return nn.Conv(features, (3, 3), padding="SAME", dtype=dtype, name=name)


class CNN(nn.Module):
    num_classes: int = 10
    width: int = 64
    dtype: jnp.dtype = jnp.float32
    stem: str = "conv"                # conv | space_to_depth
    norm: str = "group"               # group | none

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.stem not in ("conv", "space_to_depth"):
            raise ValueError(f"unknown stem {self.stem!r}")
        if self.norm not in ("group", "none"):
            raise ValueError(f"unknown norm {self.norm!r}")
        x = x.astype(self.dtype)
        if self.stem == "space_to_depth":
            x = space_to_depth(x, 2)
        folded_stages = 0
        for stage, mult in enumerate((1, 2, 4)):
            ch = self.width * mult
            # A stage of exactly half a tile of channels runs on the view
            # that fills the lanes.  Only there does the folded kernel's
            # extra matrix work (2x, on MXU columns that were idle) cost
            # less than the padding; narrower stages would execute 128/ch
            # times the products.  Explicit names pin the param paths on
            # either path.
            fold = 2 * ch == LANES and x.shape[2] % 2 == 0
            if fold:
                folded_stages += 1
                x = fold_columns(x)
            conv = FoldedConv if fold else _conv3x3
            group_norm = FoldedGroupNorm if fold else nn.GroupNorm
            for layer in (2 * stage, 2 * stage + 1):
                x = conv(ch, dtype=self.dtype, name=f"Conv_{layer}")(x)
                if self.norm == "group":
                    x = group_norm(num_groups=min(32, ch), dtype=self.dtype,
                                   name=f"GroupNorm_{layer}")(x)
                x = nn.relu(x)
            # The space_to_depth stem already halved H/W once; stop
            # pooling at 2x2 so the head still sees a spatial map.
            if x.shape[1] >= 2:
                x = (folded_max_pool(x) if fold
                     else nn.max_pool(x, (2, 2), strides=(2, 2)))
            elif fold:
                x = x.reshape(*x.shape[:2], 2 * x.shape[2], ch)
        # Set at trace time, on every build: 0 says the plain path ran.
        telemetry.get_registry().gauge("cnn.lane_folded_stages").set(
            folded_stages)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)
