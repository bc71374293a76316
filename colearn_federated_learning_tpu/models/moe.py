"""Mixture-of-Experts FFN (expert parallelism).

The reference has no MoE (SURVEY.md §2: EP absent — "models are tiny");
this layer is part of the rebuild's distributed superset and is designed
for the TPU from the start:

- **Static shapes**: routing uses the classic capacity-based one-hot
  dispatch/combine formulation (Mesh-TensorFlow / Switch Transformer
  lineage, PAPERS.md pattern only): every tensor is a fixed-size einsum
  operand, so the whole layer is jit-compatible and lands on the MXU —
  no ragged gathers, no data-dependent shapes.
- **Expert parallelism**: the expert banks are stacked ``(E, ...)`` params
  named ``experts_*``; parallel/tp.py shards their leading dim over the
  ``model`` mesh axis, and the GSPMD partitioner turns the dispatch/expert/
  combine einsums into per-shard matmuls plus the EP collectives.
- **Aux load-balance loss** (Switch: ``E · Σ_e f_e · p_e``) is ``sow``-n
  into the ``intermediates`` collection; the local trainer picks it up
  when training (fed/local.py) and it is a silent no-op everywhere else
  (flax ``sow`` does nothing when the collection is immutable).

Routing is top-2 with renormalized gates; tokens beyond an expert's
capacity ``C = ceil(top_k·N/E · capacity_factor)`` are dropped (their
block output is zero and the residual connection carries them through).
The encoder that hosts this layer is models/bert.py (``num_experts > 0``
swaps the block MLP for this module in every other block); under sequence
parallelism each sequence shard routes its LOCAL tokens with local
capacity — the standard choice, avoiding an all-to-all over the seq axis.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import sequential_vmap

from colearn_federated_learning_tpu import telemetry


class MoEFfn(nn.Module):
    """Capacity-based top-k mixture of expert FFNs over tokens."""

    embed_dim: int
    num_experts: int
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, token_mask=None):
        """``x``: (B, S, D); ``token_mask``: optional (B, S) bool, False =
        padding.  Masked tokens are excluded from routing entirely — they
        claim no expert capacity, produce zero layer output (the residual
        carries them), and do not enter the load-balance statistics."""
        B, S, D = x.shape
        E, K = self.num_experts, min(self.top_k, self.num_experts)
        F = D * self.mlp_ratio
        N = B * S
        C = max(1, int(-(-K * N * self.capacity_factor // E)))  # ceil

        xf = x.reshape(N, D)
        # Router in float32 for stable softmax; kept replicated (tp rules).
        logits = nn.Dense(E, dtype=jnp.float32, name="router")(
            xf.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)                  # (N, E)

        gate_vals, expert_idx = jax.lax.top_k(probs, K)          # (N, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )

        # (N, K, E) routing one-hot — the single source for capacity
        # accounting, dispatch, and the aux statistics.  Padding tokens are
        # zeroed BEFORE the cumsum so they never occupy a capacity slot.
        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)
        if token_mask is not None:
            mf = token_mask.reshape(N).astype(jnp.float32)        # (N,)
            onehot = onehot * token_mask.reshape(N, 1, 1).astype(jnp.int32)
        else:
            mf = jnp.ones((N,), jnp.float32)

        # Positions within each expert's buffer, rank-major: all rank-0
        # picks fill before any rank-1 pick, so primary routes win capacity.
        flat = onehot.transpose(1, 0, 2).reshape(K * N, E)       # rank-major
        pos_f = jnp.cumsum(flat, axis=0) - flat                  # (K*N, E)
        pos = (
            pos_f.reshape(K, N, E).transpose(1, 0, 2) * onehot
        ).sum(-1)                                                # (N, K)

        # dispatch (N, E, C): one-hot of (expert, position); over-capacity
        # tokens fall out because one_hot(pos >= C) is the zero row, and
        # masked tokens because their routing one-hot is already zero.
        # combine carries the gate weight on top.
        disp = (
            onehot.astype(self.dtype)[..., None]
            * jax.nn.one_hot(pos, C, dtype=self.dtype)[:, :, None, :]
        )                                                        # (N, K, E, C)
        combine = (disp * gate_vals[..., None, None].astype(self.dtype)).sum(1)
        disp = disp.sum(1)                                       # (N, E, C)

        up = self.param(
            "experts_up", nn.initializers.lecun_normal(), (E, D, F)
        ).astype(self.dtype)
        b_up = self.param(
            "experts_up_bias", nn.initializers.zeros, (E, F)
        ).astype(self.dtype)
        down = self.param(
            "experts_down", nn.initializers.lecun_normal(), (E, F, D)
        ).astype(self.dtype)
        b_down = self.param(
            "experts_down_bias", nn.initializers.zeros, (E, D)
        ).astype(self.dtype)

        xin = jnp.einsum("nec,nd->ecd", disp, xf.astype(self.dtype))
        h = nn.gelu(jnp.einsum("ecd,edf->ecf", xin, up) + b_up[:, None, :])
        y = jnp.einsum("ecf,efd->ecd", h, down) + b_down[:, None, :]
        out = jnp.einsum("nec,ecd->nd", combine, y)

        # Switch aux loss: E * sum_e fraction_routed_e * mean_prob_e over
        # PRIMARY routes of REAL tokens (minimized at uniform balance,
        # value 1.0).  Masked tokens are excluded from both statistics.
        denom = jnp.maximum(mf.sum(), 1.0)
        f_e = onehot[:, 0, :].astype(jnp.float32).sum(axis=0) / denom
        p_e = (probs * mf[:, None]).sum(axis=0) / denom
        self.sow("intermediates", "moe_aux", E * jnp.sum(f_e * p_e))

        return out.reshape(B, S, D)


# --- The share of a large mixture that one chip holds ------------------------


@sequential_vmap
def _grouped_product(rows, banks, group_sizes):
    """``rows`` (R, K) sorted by group, ``banks`` (G, K, N), ``group_sizes``
    (G,): row ``r`` of group ``g`` times ``banks[g]``.  XLA's grouped
    product visits the row tiles the group sizes cover and no others, so
    its work follows the rows routed and not ``R``; rows past their sum
    are left unwritten.  Under ``vmap`` (a client axis) it runs a client at
    a time: the chip's compiler takes no batch dimension here."""
    return lax.ragged_dot(rows, banks, group_sizes,
                          preferred_element_type=rows.dtype)


@sequential_vmap
def _grouped_product_transposed(rows, banks, group_sizes, g):
    _, pull = jax.vjp(
        lambda rows, banks: lax.ragged_dot(
            rows, banks, group_sizes, preferred_element_type=rows.dtype),
        rows, banks)
    return pull(g)


@jax.custom_vjp
def grouped_product(rows, banks, group_sizes):
    """``_grouped_product`` with its backward pass batched the same way
    (``vmap`` of a gradient meets the two rules below, not the primitive's
    own transpose, which would carry the batch dimension)."""
    return _grouped_product(rows, banks, group_sizes)


def _grouped_product_fwd(rows, banks, group_sizes):
    return (_grouped_product(rows, banks, group_sizes),
            (rows, banks, group_sizes))


def _grouped_product_bwd(kept, g):
    rows, banks, group_sizes = kept
    d_rows, d_banks = _grouped_product_transposed(rows, banks, group_sizes, g)
    return d_rows, d_banks, None


grouped_product.defvjp(_grouped_product_fwd, _grouped_product_bwd)


def held_pairs(chosen, weights, first: int, count: int):
    """The (token, choice) pairs that fell on the experts ``first .. first
    + count - 1``, sorted by expert into rows of a static bound.
    ``chosen``/``weights``: (T, k).  Returns, for ``T * min(k, count)``
    rows (every held pair is among them: a token falls on a held expert at
    most that often): each row's token (R,), its weight (R,; 0 past the
    held pairs), whether it is a held pair (R,), and the experts' group
    sizes (count,), which sum to the held pairs."""
    tokens, k = chosen.shape
    bound = tokens * min(k, count)
    local = jnp.where((chosen >= first) & (chosen < first + count),
                      chosen - first, count).reshape(-1)
    # One sort of one array: a pair's group in front of its index.
    pairs = tokens * k
    if (count + 1) * pairs >= 2 ** 31:
        raise ValueError(
            f"{tokens} tokens x {k} choices x {count} experts do not fit "
            "one int32 sort key: route fewer tokens at a time")
    order = jnp.sort(local * pairs + jnp.arange(pairs))[:bound] % pairs
    held = local[order] < count
    sizes = jnp.sum(local[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return (order // k, jnp.where(held, weights.reshape(-1)[order], 0.0),
            held, sizes)


def relu2(x):
    return jnp.square(nn.relu(x))


class LatentMoEShare(nn.Module):
    """One chip's share of a sigmoid-routed mixture whose experts work in a
    latent space (Nemotron-3's LatentMoE), beside a shared expert in the
    full width.

    The router scores all ``experts_total`` experts in float32, ``s =
    sigmoid(u W_r)``; a token's ``top_k`` experts are the largest of ``s +
    b`` (``b``, the correction bias, enters the choice alone, so its
    gradient is 0) and weigh ``w_e = scale * s_e / sum over all chosen of
    s`` (``norm_topk``), held here or not.  This chip holds the experts
    ``experts_held = (first, count)``.  With ``l = u W_down`` the result is

        (sum over e chosen and held of w_e W2_e relu(W1_e l)^2) W_up
            + W2_s relu(W1_s u)^2

    so the parts that the shares of all chips give, with the shared expert
    counted once and ``W_up`` applied to their sum, add up to the whole
    layer; what the absent experts would add is left out.

    No token is dropped and no shape depends on the routing.  Tokens go
    through in blocks of ``token_block``; in a block, the (token, choice)
    pairs are sorted by held expert (pairs that fell elsewhere last), the
    first ``token_block * min(top_k, count)`` of them (every held pair is
    among them: a token falls on a held expert at most that often) are
    gathered into rows, two grouped products over the experts' banks
    follow the group sizes (``grouped_product``), and the rows are added
    back to their tokens under their weights.  A block is a
    ``jax.checkpoint``: what its backward needs at the rows' static bound
    is made again a block at a time and not kept for all.
    """

    embed_dim: int
    latent_dim: int
    expert_dim: int
    shared_dim: int
    experts_total: int
    experts_held: tuple[int, int]
    top_k: int
    routed_scale: float = 1.0
    token_block: int = 4096
    dtype: jnp.dtype = jnp.float32
    init_std: float = 0.02
    out_scale: float = 1.0          # on the maps back into the stream

    def setup(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.experts_total):
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the "
                f"{self.experts_total} experts")
        if self.top_k > self.experts_total:
            raise ValueError(f"top_k {self.top_k} of {self.experts_total}")
        init = nn.initializers.normal(self.init_std)
        out_init = nn.initializers.normal(self.init_std * self.out_scale)
        D, Z, F = self.embed_dim, self.latent_dim, self.expert_dim
        self.router = self.param("router", init, (D, self.experts_total))
        self.router_bias = self.param(
            "router_bias", nn.initializers.zeros, (self.experts_total,))
        self.latent_down = self.param("latent_down", init, (D, Z))
        self.latent_up = self.param("latent_up", out_init, (Z, D))
        self.experts_w1 = self.param("experts_w1", init, (count, Z, F))
        self.experts_w2 = self.param("experts_w2", init, (count, F, Z))
        self.shared_w1 = self.param("shared_w1", init, (D, self.shared_dim))
        self.shared_w2 = self.param("shared_w2", out_init,
                                    (self.shared_dim, D))

    def route(self, u32):
        """``u32``: (N, D) float32.  The chosen experts (N, top_k) and
        their weights (N, top_k) float32."""
        scores = nn.sigmoid(jnp.dot(
            u32, self.router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(scores + self.router_bias, self.top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = self.routed_scale * picked / picked.sum(-1, keepdims=True)
        return chosen, weights

    def _block(self, latent, chosen, weights):
        """The held experts' part for one block of tokens: ``latent`` (T,
        Z), ``chosen``/``weights`` (T, top_k).  Returns (T, Z)."""
        token, weight, held, sizes = held_pairs(
            chosen, weights, *self.experts_held)
        # Rows past the group sizes' sum are unwritten by the products:
        # nothing of them may pass, forward or backward.
        rows = jnp.where(held[:, None], latent[token], 0)
        hidden = relu2(grouped_product(
            rows, self.experts_w1.astype(self.dtype), sizes))
        out = grouped_product(
            hidden, self.experts_w2.astype(self.dtype), sizes)
        out = jnp.where(held[:, None], out, 0).astype(
            jnp.float32) * weight[:, None]
        return jnp.zeros(latent.shape, jnp.float32).at[token].add(
            out).astype(self.dtype)

    def routed_latent(self, u, u32):
        """``sum over e chosen and held of w_e E_e(l)``, (N, Z): the part
        of the layer that differs from share to share."""
        tokens = u.shape[0]
        block = min(self.token_block, tokens)
        if tokens % block:
            raise ValueError(
                f"{tokens} tokens are not whole blocks of {block}")
        count = self.experts_held[1]
        # Set at trace time, on every build.
        registry = telemetry.get_registry()
        registry.gauge("moe.experts_held").set(count)
        registry.gauge("moe.experts_total").set(self.experts_total)
        registry.gauge("moe.top_k").set(self.top_k)
        registry.gauge("moe.dispatch_rows").set(
            tokens * min(self.top_k, count))
        chosen, weights = self.route(u32)
        latent = jnp.dot(u, self.latent_down.astype(self.dtype))

        def blocks(a):
            return a.reshape(tokens // block, block, *a.shape[1:])

        out = lax.map(
            lambda args: jax.checkpoint(self._block)(*args),
            (blocks(latent), blocks(chosen), blocks(weights)))
        return out.reshape(tokens, -1)

    def shared(self, u):
        return jnp.dot(relu2(jnp.dot(u, self.shared_w1.astype(self.dtype))),
                       self.shared_w2.astype(self.dtype))

    def __call__(self, u32):
        """``u32``: (..., D), the normed stream in float32 (the router
        reads it unrounded).  Returns (..., D) in ``dtype``."""
        lead = u32.shape[:-1]
        u32 = u32.reshape(-1, u32.shape[-1]).astype(jnp.float32)
        u = u32.astype(self.dtype)
        out = jnp.dot(self.routed_latent(u, u32),
                      self.latent_up.astype(self.dtype)) + self.shared(u)
        return out.reshape(*lead, -1)
