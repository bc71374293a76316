"""Plain reference for the ``cnn`` family: forward pass and loss in float32
``jax.numpy``, written from the architecture, not from the program.

The architecture (BASELINE.json config #2, as ``models/cnn.py`` builds it
with ``stem="conv"``, ``norm="group"``): three stages at ``width`` x 1, 2,
4 channels; a stage is two 3x3 same-padded convolutions with bias, each
followed by GroupNorm (``min(32, channels)`` groups, eps 1e-6, per-channel
scale and bias) and ReLU, then a 2x2 max-pool of stride 2; the mean over
the remaining positions feeds one dense layer to the classes.  The loss
is the mean softmax cross-entropy.

Weights arrive under the program's parameter names (``Conv_i``,
``GroupNorm_i``, ``Dense_0``; kernels HWIO and (in, out)), because they
are the program's own seeded initial weights.  Nothing here imports the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

GROUP_NORM_EPS = 1e-6

# How far the program may be from this reference (benchmarks/harness/
# probe.py says what is compared).  The program computes its activations
# in bf16: 8 significant bits, 2^-8 = 3.9e-3 relative per rounding.
# - loss 5e-3: a mean over 32 images of a float32 log-softmax; measured
#   gap 3e-6 to 5.1e-4 on the v5e across 12 seeds (PERF.md section 6).  A
#   forward matrix product in fp8 (3 explicit bits, 32 times coarser)
#   moves it by a few per cent, a dropped GroupNorm by tens.
# - gradient, per leaf 0.25 over a floor of 0.05: ReLU and max-pool are
#   discrete, so a bf16 rounding flips which position gets the gradient,
#   and the early convolutions sit at 10 to 20% (measured per-leaf maximum
#   0.08 to 0.16 on the v5e, always Conv_1's kernel).  That catches a wrong
#   or dropped backward term (gaps near 1), not a backward product in fp8
#   in an early layer; the loss tolerance is what holds the precision.
TOLERANCE = {"loss": 5e-3, "grad_leaf": 0.25, "grad_floor": 0.05}


def conv3x3(x, kernel, bias):
    """Same-padded 3x3 convolution as nine shifted matrix products."""
    n, h, w, _ = x.shape
    padded = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = bias
    for dy in range(3):
        for dx in range(3):
            out = out + padded[:, dy:dy + h, dx:dx + w, :] @ kernel[dy, dx]
    return out


def group_norm(x, scale, bias, groups):
    n, h, w, c = x.shape
    g = x.reshape(n, h * w, groups, c // groups)
    mean = g.mean(axis=(1, 3), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + GROUP_NORM_EPS)
    return g.reshape(n, h, w, c) * scale + bias


def max_pool2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(params, x, model: dict):
    """Logits for images ``x`` (N, H, W, C); ``model`` is the
    configuration's ``experiment.model`` section."""
    if model.get("stem", "conv") != "conv" or model.get("norm", "group") != "group":
        raise ValueError("the reference covers stem='conv', norm='group'")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(x)
    layer = 0
    for mult in (1, 2, 4):
        channels = model["width"] * mult
        for _ in range(2):
            conv, norm = params[f"Conv_{layer}"], params[f"GroupNorm_{layer}"]
            x = conv3x3(x, f32(conv["kernel"]), f32(conv["bias"]))
            x = group_norm(x, f32(norm["scale"]), f32(norm["bias"]),
                           min(32, channels))
            x = jnp.maximum(x, 0.0)
            layer += 1
        if x.shape[1] >= 2:
            x = max_pool2(x)
    dense = params["Dense_0"]
    return x.mean(axis=(1, 2)) @ f32(dense["kernel"]) + f32(dense["bias"])


def loss(params, x, y, model: dict):
    logp = jax.nn.log_softmax(forward(params, x, model))
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
