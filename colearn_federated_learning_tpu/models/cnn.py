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
import jax.numpy as jnp


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
        for mult in (1, 2, 4):
            ch = self.width * mult
            x = nn.Conv(ch, (3, 3), padding="SAME", dtype=self.dtype)(x)
            if self.norm == "group":
                x = nn.GroupNorm(num_groups=min(32, ch), dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.Conv(ch, (3, 3), padding="SAME", dtype=self.dtype)(x)
            if self.norm == "group":
                x = nn.GroupNorm(num_groups=min(32, ch), dtype=self.dtype)(x)
            x = nn.relu(x)
            # The space_to_depth stem already halved H/W once; stop
            # pooling at 2x2 so the head still sees a spatial map.
            if x.shape[1] >= 2:
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)
