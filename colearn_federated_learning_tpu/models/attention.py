"""Multi-head attention with a pluggable core.

The projections (q/k/v/out) are ordinary ``nn.DenseGeneral`` matmuls — the
MXU work — and are IDENTICAL across cores, so the param pytree does not
depend on which core computes the softmax:

- ``dense``: single-device reference einsum (parallel/ring.py oracle).
- ``flash``: Pallas blockwise kernel (ops/attention.py) — no (L, L) matrix
  in HBM; interpret mode off-TPU.
- ``ring``:  sequence-parallel ring attention — REQUIRES being called
  inside ``shard_map`` with the sequence dim sharded over ``axis_name``
  (parallel/sp.py drives this).
- ``ulysses``: sequence-parallel all-to-all attention (heads re-sharded
  across the axis; parallel/ulysses.py) — same shard_map contract as
  ``ring``, needs ``num_heads`` divisible by the axis size.

Selected per-model via ``ModelConfig.attn_impl``.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

ATTN_IMPLS = ("dense", "flash", "ring", "ulysses")


def grouped_query(num_heads: int, num_kv_heads: Optional[int]) -> int:
    """Query heads to a key/value head; 1 when they are as many."""
    if num_kv_heads is None:
        return 1
    if num_kv_heads < 1 or num_heads % num_kv_heads:
        raise ValueError(
            f"{num_heads} query heads do not share {num_kv_heads} key/value "
            "heads evenly")
    return num_heads // num_kv_heads


class MultiHeadAttention(nn.Module):
    num_heads: int
    dtype: jnp.dtype = jnp.float32
    impl: str = "dense"
    axis_name: Optional[str] = None   # mesh axis (impl="ring"/"ulysses")
    causal: bool = False
    # Grouped-query attention: fewer key/value heads than query heads,
    # query head h reading key/value head h // (num_heads // num_kv_heads);
    # a head size that is not embed dim / heads; projections without bias
    # or with another initialisation.  The defaults are the equal-head
    # layer this module always was.
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    use_bias: bool = True
    kernel_init: Callable = nn.linear.default_kernel_init
    out_kernel_init: Callable = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, x, kv_mask=None):
        """x: (B, L, D); kv_mask: optional (B, L) bool, False = padding."""
        D = x.shape[-1]
        head_dim = self.head_dim
        if head_dim is None:
            if D % self.num_heads:
                raise ValueError(f"embed dim {D} not divisible by {self.num_heads} heads")
            head_dim = D // self.num_heads
        group = grouped_query(self.num_heads, self.num_kv_heads)

        proj = lambda name, heads=self.num_heads: nn.DenseGeneral(  # noqa: E731
            features=(heads, head_dim), dtype=self.dtype, name=name,
            use_bias=self.use_bias, kernel_init=self.kernel_init
        )
        q = proj("query")(x)
        k = proj("key", self.num_heads // group)(x)
        v = proj("value", self.num_heads // group)(x)
        if group > 1 and self.impl != "flash":
            # The flash path shares the heads itself (ops/attention.py).
            k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))

        if self.impl == "dense":
            from colearn_federated_learning_tpu.parallel.ring import dense_attention

            out = dense_attention(q, k, v, kv_mask, causal=self.causal)
        elif self.impl == "flash":
            from colearn_federated_learning_tpu.ops.attention import flash_attention

            out = flash_attention(q, k, v, kv_mask, causal=self.causal)
        elif self.impl == "ring":
            from colearn_federated_learning_tpu.parallel.ring import ring_attention

            if not self.axis_name:
                raise ValueError("impl='ring' needs axis_name (a mesh axis)")
            out = ring_attention(q, k, v, kv_mask, axis_name=self.axis_name,
                                 causal=self.causal)
        elif self.impl == "ulysses":
            from colearn_federated_learning_tpu.parallel.ulysses import (
                ulysses_attention,
            )

            if not self.axis_name:
                raise ValueError("impl='ulysses' needs axis_name (a mesh axis)")
            out = ulysses_attention(q, k, v, kv_mask,
                                    axis_name=self.axis_name,
                                    causal=self.causal)
        else:
            raise ValueError(f"unknown attn impl {self.impl!r}; use {ATTN_IMPLS}")

        return nn.DenseGeneral(features=D, axis=(-2, -1), dtype=self.dtype,
                               use_bias=self.use_bias, name="out",
                               kernel_init=self.out_kernel_init)(out)
