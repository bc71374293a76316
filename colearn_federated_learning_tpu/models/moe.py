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

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.custom_batching import sequential_vmap

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.ops.attention import FLASH_RESIDUAL_NAMES


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


def relu2(x):
    return jnp.square(nn.relu(x))


def gated(u, w_gate, w_up, w_down, product=jnp.dot, limit: float = 0.0):
    """``W_d (silu(W_g u) * W_u u)``; ``product`` multiplies rows by a
    matrix.  With a ``limit`` c > 0 the gate's pre-activation is clipped
    above at c and the up-projection to [-c, c]."""
    gate = product(u, w_gate)
    if limit > 0:
        gate = jnp.minimum(gate, limit)
    hidden, up = nn.silu(gate), product(u, w_up)
    if limit > 0:
        up = jnp.clip(up, -limit, limit)
    return product(hidden * up, w_down)


def tile_sizes(sizes, start, rows: int):
    """What the rows ``start .. start + rows - 1`` hold of each group,
    (G,), where ``sizes`` (G,) are the groups' sizes and the groups lie one
    after another from row 0: they sum to the part of the range that lies
    before the groups' end."""
    ends = jnp.cumsum(sizes)
    return (jnp.clip(ends, start, start + rows)
            - jnp.clip(ends - sizes, start, start + rows))


def relu2_experts(rows, sizes, w1, w2):
    """``W2_e relu(W1_e l)^2`` for rows sorted by expert: the banks ``w1``
    (G, Z, F), ``w2`` (G, F, Z) in a latent space (Nemotron-3's
    LatentMoE)."""
    hidden = relu2(lax.ragged_dot(rows, w1, sizes,
                                  preferred_element_type=rows.dtype))
    return lax.ragged_dot(hidden, w2, sizes,
                          preferred_element_type=rows.dtype)


def gated_experts(rows, sizes, w_gate, w_up, w_down, limit: float = 0.0):
    """``W_d (silu(W_g u) * W_u u)`` for rows sorted by expert: three banks
    (G, C, F), (G, C, F), (G, F, C) in the stream's own width; ``limit`` as
    ``gated`` takes it."""
    return gated(rows, w_gate, w_up, w_down, product=lambda a, bank:
                 lax.ragged_dot(a, bank, sizes,
                                preferred_element_type=rows.dtype),
                 limit=limit)


@functools.lru_cache(maxsize=None)
def gated_experts_within(limit: float):
    """``gated_experts`` under a clamp, one function a limit (it is a static
    argument of ``routed_rows``)."""
    return functools.partial(gated_experts, limit=limit)


def _tile(experts, acc, latent, weight, banks, token, held, sizes):
    """One tile of rows sorted by expert, added to their tokens: ``acc``,
    ``latent`` (T, Z); ``token``, ``weight``, ``held`` (R,) as
    ``held_pairs`` gives them; ``banks``, the experts' weights with the
    held experts in front, and ``experts`` the function that takes rows
    through them (``relu2_experts``, ``gated_experts``); ``sizes`` (G,),
    what the tile holds of each expert.  A tile may span several experts,
    and an expert several tiles.  XLA's grouped product visits the row
    tiles the group sizes cover and leaves the rows past their sum
    unwritten: nothing of those may pass, forward or backward (``held`` is
    False there, and ``weight`` 0).  Returns ``acc`` plus, at every token
    of the tile, ``weight * E_e(latent)``, (T, Z) float32."""
    rows = jnp.where(held[:, None], latent[token], 0)
    out = experts(rows, sizes, *banks)
    out = jnp.where(held[:, None], out, 0).astype(
        jnp.float32) * weight[:, None]
    return acc.at[token].add(out)


def _tile_at(i, tile: int, token, weight, held, sizes):
    """Tile ``i``'s slice of the rows' vectors and its group sizes."""
    return (*(lax.dynamic_slice(a, (i * tile,), (tile,))
              for a in (token, weight, held)),
            tile_sizes(sizes, i * tile, tile))


def _tiles_to_visit(sizes, tile: int):
    return (jnp.sum(sizes) + tile - 1) // tile


def visit_row_tiles(tile: int, latent, weight, *rest,
                    experts=relu2_experts):
    """The held experts' part for blocks of tokens, (B, T, Z) float32, and
    the tiles visited in each, (B,).  ``latent`` (B, T, Z); ``rest`` is the
    experts' banks (for ``relu2_experts`` (G, Z, F) and (G, F, Z)), rounded
    to ``latent``'s precision once, and after them a block's rows:
    ``held_pairs``' vectors ``token``, ``weight``, ``held`` (B, R; whole
    tiles of ``tile``) and ``sizes`` (B, G).  A block's rows go through
    ``_tile`` in a loop whose trip count is its held pairs
    (``sizes.sum()``) over ``tile``, rounded up, a value on the device.  The
    rows past the last visited tile are never read and never written back;
    at a routing that fills the rows' static bound, every tile is
    visited."""
    *banks, token, held, sizes = rest
    banks = tuple(w.astype(latent.dtype) for w in banks)

    def block(rows):
        latent, weight, token, held, sizes = rows
        tiles = _tiles_to_visit(sizes, tile)

        def visit(i, acc):
            token_i, weight_i, held_i, sizes_i = _tile_at(
                i, tile, token, weight, held, sizes)
            return _tile(experts, acc, latent, weight_i, banks, token_i,
                         held_i, sizes_i)

        return lax.fori_loop(
            0, tiles, visit, jnp.zeros(latent.shape, jnp.float32)), tiles

    return lax.map(block, (latent, weight, token, held, sizes))


def _visit_row_tiles_transposed(tile: int, experts, latent, weight, banks,
                                token, held, sizes, g):
    """``g`` (B, T, Z) float32 pulled back through ``visit_row_tiles`` to
    ``latent``, ``weight`` and the banks: the same loops, each visit
    pulling its block's ``g`` back through its tile (``jax.vjp`` of
    ``_tile``).  A tile's part of ``d weight`` is its own slice; the banks'
    are summed in float32 over every tile of every block, in their own
    precision and not the products'."""
    rounded = tuple(w.astype(latent.dtype) for w in banks)

    def block(d_banks, rows):
        latent, weight, token, held, sizes, g = rows

        def visit(i, sums):
            token_i, weight_i, held_i, sizes_i = _tile_at(
                i, tile, token, weight, held, sizes)
            _, pull = jax.vjp(
                lambda latent, weight_i, *banks: _tile(
                    experts, jnp.zeros_like(g), latent, weight_i, banks,
                    token_i, held_i, sizes_i),
                latent, weight_i, *rounded)
            d_latent, d_weight_i, *d_tile = pull(g)
            return (sums[0] + d_latent,
                    lax.dynamic_update_slice(sums[1], d_weight_i, (i * tile,)),
                    *(total + d for total, d in zip(sums[2:], d_tile)))

        d_latent, d_weight, *d_banks = lax.fori_loop(
            0, _tiles_to_visit(sizes, tile), visit,
            (jnp.zeros(latent.shape, jnp.float32), jnp.zeros_like(weight),
             *d_banks))
        return tuple(d_banks), (d_latent.astype(latent.dtype), d_weight)

    d_banks, (d_latent, d_weight) = lax.scan(
        block, tuple(jnp.zeros(w.shape, jnp.float32) for w in banks),
        (latent, weight, token, held, sizes, g))
    return d_latent, d_weight, tuple(
        d.astype(w.dtype) for d, w in zip(d_banks, banks))


def _a_client_at_a_time(visit):
    """Under ``vmap`` (a client axis) a loop whose trip count differs from
    client to client would run every client to the longest, and the chip's
    compiler takes no batch dimension on a grouped product: ``visit`` runs
    a client at a time."""
    return sequential_vmap(visit)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def routed_rows(tile: int, experts, latent, weight, banks, token, held,
                sizes):
    """``visit_row_tiles``' answer, differentiable in ``latent``, ``weight``
    and the banks (a tuple): a loop with a trip count on the device has no
    reverse-mode rule of its own.  The backward is handed the arguments
    and nothing else, so it never needs the loop's output: a rematerialised
    layer that keeps the arguments and the output (``SHARE_RESIDUAL_NAMES``)
    runs the backward's loop alone.  ``vmap`` of a gradient meets the two
    rules below, each a client at a time, and no primitive's own."""
    return _routed_rows_fwd(tile, experts, latent, weight, banks, token,
                            held, sizes)[0]


def _routed_rows_fwd(tile, experts, latent, weight, banks, token, held,
                     sizes):
    visit = functools.partial(visit_row_tiles, tile, experts=experts)
    with telemetry.device_scope("moe.tiles"):
        out, _ = _a_client_at_a_time(
            lambda latent, weight, banks, *rows: visit(
                latent, weight, *banks, *rows))(
            latent, weight, banks, token, held, sizes)
    return out, (latent, weight, banks, token, held, sizes)


def _routed_rows_bwd(tile, experts, args, g):
    # The backward pass is traced apart from the forward's scopes.
    with telemetry.device_scope("moe.tiles"):
        return (*_a_client_at_a_time(functools.partial(
            _visit_row_tiles_transposed, tile, experts))(*args, g),
                None, None, None)


routed_rows.defvjp(_routed_rows_fwd, _routed_rows_bwd)


@jax.custom_vjp
def _sorted_with(keys, values):
    """``keys`` (N,), no two alike, in rising order and ``values`` (N,) in
    the order that puts them in: one sort of the two side by side,
    differentiable in ``values``."""
    return lax.sort((keys, values), num_keys=1, is_stable=False)


def _sorted_with_fwd(keys, values):
    keys, values = _sorted_with(keys, values)
    return (keys, values), keys


def _sorted_with_bwd(place, g):
    """``held_pairs``' keys, sorted, name the cell each came from: a second
    sort on those takes ``g`` back, and nothing is scattered."""
    return None, lax.sort((lax.rem(place, place.shape[-1]), g[1]), num_keys=1,
                          is_stable=False)[1]


_sorted_with.defvjp(_sorted_with_fwd, _sorted_with_bwd)


def picked_scores(scores, chosen):
    """``scores[t, chosen[t, c]]``, (T, k), to the last bit: over the
    expert axis, the one score whose expert is the choice added to zeros.
    Comparisons, selects and a sum that fuse, forward and backward; a
    gather or a scatter of scalars the chip takes an element at a time."""
    experts = jnp.arange(scores.shape[-1])
    return jnp.sum(jnp.where(chosen[..., None] == experts,
                             scores[..., None, :], 0), axis=-1)


def pair_sort_keys(tokens: int, count: int) -> int:
    """The keys ``held_pairs`` sorts for a block of ``tokens``."""
    return count * tokens


def held_pairs(chosen, weights, first: int, count: int):
    """The (token, choice) pairs that fell on the experts ``first .. first
    + count - 1``, sorted by expert into rows of a static bound.
    ``chosen``/``weights``: (T, k); a token's choices differ.  Returns, for
    ``T * min(k, count)`` rows (every held pair is among them: a token
    falls on a held expert at most that often): each row's token (R,), its
    weight (R,; 0 past the held pairs), whether it is a held pair (R,), and
    the experts' group sizes (count,), which sum to the held pairs.

    The rows are the True cells of the membership table (count, T), expert
    by expert, read in order.  A cell's key is its own index where held and
    past every index where not, so one sort of ``count * T`` keys puts the
    held cells in front; a row's token is its key modulo T, and its weight
    rides through the sort beside the key.  Everything else is comparisons,
    selects and sums over dense arrays: no scalar is gathered or scattered,
    forward or backward."""
    tokens, k = chosen.shape
    bound = tokens * min(k, count)
    cells = pair_sort_keys(tokens, count)
    if 2 * cells >= 2 ** 31:
        raise ValueError(
            f"{tokens} tokens x {count} experts do not fit one int32 sort "
            "key: route fewer tokens at a time")
    on = chosen[None] == (first + jnp.arange(count))[:, None, None]
    member = on.any(-1)                                     # (count, T)
    # At most one choice of a token is an expert: its weight plus zeros.
    table = jnp.sum(jnp.where(on, weights[None], 0), axis=-1)
    cell = jnp.arange(cells)
    keys, weight = _sorted_with(
        jnp.where(member.reshape(-1), cell, cells + cell), table.reshape(-1))
    keys, weight = keys[:bound], weight[:bound]
    return (lax.rem(keys, tokens), weight, keys < cells,
            jnp.sum(member, axis=-1, dtype=jnp.int32))


def _pairs_of_blocks(experts_held, tile: int, chosen, weights):
    token, weight, held, sizes = lax.map(
        lambda pairs: held_pairs(*pairs, *experts_held), (chosen, weights))
    # Whole tiles; what is added holds no pair.
    token, weight, held = (
        jnp.pad(a, ((0, 0), (0, -a.shape[1] % tile)))
        for a in (token, weight, held))
    return token, weight, held, sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def block_pairs(experts_held, tile: int, chosen, weights):
    """``held_pairs`` of every block, ``chosen``/``weights`` (B, T, k), the
    rows padded to whole tiles of ``tile``.  Differentiable in ``weights``
    by ``held_pairs``' own rules; it is a rule of its own for the names'
    sake: a checkpoint policy sees the names at a block's top level and not
    those inside ``lax.map``, so the map's pull-back is taken whole
    (``jax.vjp``) and the arrays it holds, the sorted keys among them, are
    named beside the four results."""
    return _pairs_of_blocks(experts_held, tile, chosen, weights)


def _block_pairs_fwd(experts_held, tile, chosen, weights):
    rows, pull = jax.vjp(functools.partial(
        _pairs_of_blocks, experts_held, tile, chosen), weights)
    rows = tuple(checkpoint_name(a, name) for a, name in zip(rows, (
        "moe_row_token", "moe_row_weight", "moe_row_held",
        "moe_group_sizes")))
    return rows, jax.tree.map(
        lambda a: checkpoint_name(a, "moe_pairs_pullback"), pull)


def _block_pairs_bwd(experts_held, tile, pull, g):
    return (None, *pull(g))


block_pairs.defvjp(_block_pairs_fwd, _block_pairs_bwd)


# What a share layer's backward and the layer around it read of its forward:
# names a ``jax.checkpoint`` policy may ask for (``save_only_these_names``,
# as ``ops/attention.py`` ``FLASH_RESIDUAL_NAMES``).  A rematerialised layer
# that keeps them runs neither the router's product, nor the choice, nor the
# pairs' sort, nor the loop over the row tiles a second time.  Under any
# other policy, and outside a checkpoint, a name is the identity.
SHARE_RESIDUAL_NAMES = (
    "moe_logits", "moe_chosen", "moe_picked",            # route
    "moe_row_token", "moe_row_weight", "moe_row_held",   # block_pairs
    "moe_group_sizes", "moe_pairs_pullback",
    "moe_routed_rows")                                   # routed


def remat_but_for_named(block_cls, remat: bool, more_names=()):
    """``block_cls``, a layer that may hold a share layer, made again in its
    backward pass if ``remat``, but for the flash kernel's named results,
    the share layer's and ``more_names`` (another mixer's, ``ops/kda.py``).
    The gauge is set at trace time, on every build."""
    telemetry.get_registry().gauge("moe.remat_saved_arrays").set(
        len(SHARE_RESIDUAL_NAMES) if remat else 0)
    if not remat:
        return block_cls
    return nn.remat(
        block_cls, policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUAL_NAMES, *SHARE_RESIDUAL_NAMES, *more_names))


class ExpertShare(nn.Module):
    """What the shares of a sigmoid-routed mixture have in common, whatever
    their experts compute: the router over all the experts, the rows of
    the held ones, the loop over their tiles.  A subclass declares the
    sizes (``embed_dim``, ``experts_total``, ``experts_held``, ``top_k``,
    ``routed_scale``, ``token_block``, ``row_tile``, ``dtype``,
    ``init_std``, ``n_group``, ``topk_group``) and, in ``setup``, calls
    ``setup_router``.

    The router scores all ``experts_total`` experts in float32, ``s =
    sigmoid(u W_r)``; a token's ``top_k`` experts are the largest of ``s +
    b`` (``b``, the correction bias, enters the choice alone, so its
    gradient is 0), with ``n_group`` > 1 among the experts of its
    ``topk_group`` best groups (``within_kept_groups``), and weigh ``w_e = scale * s_e / sum over all chosen of
    s`` (``norm_topk``), held here or not.  This chip holds the experts
    ``experts_held = (first, count)``.

    No token is dropped and no shape depends on the routing.  Tokens go
    through in blocks of ``token_block``; in a block, the (token, choice)
    pairs that fell on a held expert are put in rows expert by expert by
    one sort of the block's membership table, ``count * token_block`` keys
    (``held_pairs``), and the first ``token_block * min(top_k, count)``
    rows are the block's (every held pair is among them: a token falls on
    a held expert at most that often).  Which pair goes where is computed
    from dense arrays by comparisons, selects and sums that fuse, forward
    and backward: the chip gathers and scatters scalars an element at a
    time.  That bound is the length of four vectors and of nothing else:
    the rows are gathered, taken through the
    grouped products over the experts' banks and added back to their
    tokens under their weights a tile of ``row_tile`` at a time, by a loop
    that visits the tiles the held pairs fill and no others
    (``visit_row_tiles``), so the work follows the rows routed up to the
    bound, where it visits them all.  The loop's trip count is a value on
    the device, so the blocks' loops are one ``jax.custom_vjp``
    (``routed_rows``): its backward is handed the forward's arguments and
    runs the same loops, a tile's forward made again inside them.

    Under ``nn.remat`` the layer names what its backward and the layer
    around it read (``SHARE_RESIDUAL_NAMES``): the router's product, the
    choice and the chosen scores, the pairs' rows with their pull-back, and
    the routed rows.  A policy that keeps those names leaves the
    rematerialised layer the norm, the sigmoid and the maps around the
    experts; the routing and the tile loop run once forward and once
    backward.
    """

    def setup_router(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.experts_total):
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the "
                f"{self.experts_total} experts")
        if self.top_k > self.experts_total:
            raise ValueError(f"top_k {self.top_k} of {self.experts_total}")
        per_group, left = divmod(self.experts_total, self.n_group)
        if (left or not 1 <= self.topk_group <= self.n_group
                or (self.n_group > 1 and per_group < 2)
                or self.top_k > self.topk_group * per_group):
            raise ValueError(
                f"{self.top_k} of {self.experts_total} experts cannot be "
                f"chosen within {self.topk_group} of {self.n_group} groups")
        self.router = self.param(
            "router", nn.initializers.normal(self.init_std),
            (self.embed_dim, self.experts_total))
        self.router_bias = self.param(
            "router_bias", nn.initializers.zeros, (self.experts_total,))

    def within_kept_groups(self, biased):
        """``biased`` (N, experts_total), the scores the choice is made on,
        with minus infinity outside a token's ``topk_group`` best groups of
        ``n_group``: the experts are ``n_group`` groups one after another,
        and a group's score is the sum of its two largest (DeepSeek-V3's
        ``noaux_tc``).  One group: as it came."""
        if self.n_group == 1:
            return biased
        grouped = biased.reshape(biased.shape[0], self.n_group, -1)
        group_scores = lax.top_k(grouped, 2)[0].sum(-1)       # (N, n_group)
        best = lax.top_k(group_scores, self.topk_group)[1]
        kept = (best[..., None] == jnp.arange(self.n_group)).any(-2)
        return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
            biased.shape)

    def route(self, u32):
        """``u32``: (N, D) float32.  The chosen experts (N, top_k) and
        their weights (N, top_k) float32."""
        with telemetry.device_scope("moe.route"):
            logits = checkpoint_name(jnp.dot(
                u32, self.router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), "moe_logits")
            scores = nn.sigmoid(logits)
            chosen = checkpoint_name(
                lax.top_k(self.within_kept_groups(
                    scores + self.router_bias), self.top_k)[1],
                "moe_chosen")
            picked = checkpoint_name(picked_scores(scores, chosen),
                                     "moe_picked")
            weights = (self.routed_scale * picked
                       / picked.sum(-1, keepdims=True))
        return chosen, weights

    def routed(self, experts, banks, chosen, weights, rows_in):
        """``sum over e chosen and held of w_e E_e(rows_in)``, (N, Z) in
        ``dtype``: ``chosen`` and ``weights`` are ``route``'s, ``rows_in``
        (N, Z) is what the experts read, ``experts`` and ``banks`` as
        ``_tile`` takes them."""
        tokens = rows_in.shape[0]
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
        registry.gauge("moe.groups").set(self.n_group)
        registry.gauge("moe.groups_kept").set(self.topk_group)
        bound = min(self.top_k, count)
        tile = min(self.row_tile, block * bound)
        registry.gauge("moe.dispatch_rows").set(tokens * bound)
        registry.gauge("moe.row_tile").set(tile)
        registry.gauge("moe.pair_sort_keys").set(pair_sort_keys(block, count))

        def blocks(a):
            return a.reshape(tokens // block, block, *a.shape[1:])

        with telemetry.device_scope("moe.pairs"):
            token, weight, held, sizes = block_pairs(
                tuple(self.experts_held), tile, blocks(chosen),
                blocks(weights))
        with telemetry.device_scope("moe.tiles"):
            out = routed_rows(tile, experts, blocks(rows_in), weight, banks,
                              token, held, sizes)
            return checkpoint_name(out.astype(self.dtype),
                                   "moe_routed_rows").reshape(tokens, -1)


class LatentMoEShare(ExpertShare):
    """One chip's share of a mixture whose experts work in a latent space
    (Nemotron-3's LatentMoE), beside a shared expert in the full width.
    With ``l = u W_down`` the result is

        (sum over e chosen and held of w_e W2_e relu(W1_e l)^2) W_up
            + W2_s relu(W1_s u)^2

    so the parts that the shares of all chips give, with the shared expert
    counted once and ``W_up`` applied to their sum, add up to the whole
    layer; what the absent experts would add is left out.
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
    # Whole tiles of the grouped product (512 rows).  As many rows as a block
    # has tokens: one visit while a token falls on one held expert or fewer.
    row_tile: int = 4096
    dtype: jnp.dtype = jnp.float32
    init_std: float = 0.02
    out_scale: float = 1.0          # on the maps back into the stream
    n_group: int = 1                # the choice within topk_group of them
    topk_group: int = 1

    def setup(self):
        self.setup_router()
        count = self.experts_held[1]
        init = nn.initializers.normal(self.init_std)
        out_init = nn.initializers.normal(self.init_std * self.out_scale)
        D, Z, F = self.embed_dim, self.latent_dim, self.expert_dim
        self.latent_down = self.param("latent_down", init, (D, Z))
        self.latent_up = self.param("latent_up", out_init, (Z, D))
        self.experts_w1 = self.param("experts_w1", init, (count, Z, F))
        self.experts_w2 = self.param("experts_w2", init, (count, F, Z))
        self.shared_w1 = self.param("shared_w1", init, (D, self.shared_dim))
        self.shared_w2 = self.param("shared_w2", out_init,
                                    (self.shared_dim, D))

    def routed_latent(self, u, u32):
        """``sum over e chosen and held of w_e E_e(l)``, (N, Z): the part
        of the layer that differs from share to share."""
        return self.routed(
            relu2_experts, (self.experts_w1, self.experts_w2),
            *self.route(u32),
            jnp.dot(u, self.latent_down.astype(self.dtype)))

    def shared(self, u):
        with telemetry.device_scope("moe.shared"):
            return jnp.dot(
                relu2(jnp.dot(u, self.shared_w1.astype(self.dtype))),
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


class GatedMoEShare(ExpertShare):
    """One chip's share of a mixture of gated experts in the stream's own
    width (DeepSeek-V3's form, arXiv:2412.19437), beside a shared expert of
    the same form:

        sum over e chosen and held of w_e E_e(u) + E_s(u),
        E(u) = W_d (silu(W_g u) * W_u u)

    There are no latent maps: the parts that the shares of all chips give,
    with the shared expert counted once, add up to the whole layer.
    """

    embed_dim: int
    expert_dim: int
    shared_dim: int
    experts_total: int
    experts_held: tuple[int, int]
    top_k: int
    routed_scale: float = 1.0
    token_block: int = 4096
    row_tile: int = 4096
    dtype: jnp.dtype = jnp.float32
    init_std: float = 0.02
    out_scale: float = 1.0          # on the maps back into the stream
    n_group: int = 1                # the choice within topk_group of them
    topk_group: int = 1
    # Clamps on the experts' and the shared expert's gate and up-projection
    # (``gated``); 0: none.
    expert_limit: float = 0.0
    shared_limit: float = 0.0

    def setup(self):
        self.setup_router()
        count = self.experts_held[1]
        init = nn.initializers.normal(self.init_std)
        out_init = nn.initializers.normal(self.init_std * self.out_scale)
        D, F, S = self.embed_dim, self.expert_dim, self.shared_dim
        self.experts_gate = self.param("experts_gate", init, (count, D, F))
        self.experts_up = self.param("experts_up", init, (count, D, F))
        self.experts_down = self.param("experts_down", out_init,
                                       (count, F, D))
        self.shared_gate = self.param("shared_gate", init, (D, S))
        self.shared_up = self.param("shared_up", init, (D, S))
        self.shared_down = self.param("shared_down", out_init, (S, D))

    def routed_part(self, u, u32):
        """``sum over e chosen and held of w_e E_e(u)``, (N, D): the part
        of the layer that differs from share to share."""
        experts = (gated_experts_within(self.expert_limit)
                   if self.expert_limit > 0 else gated_experts)
        return self.routed(
            experts, (self.experts_gate, self.experts_up, self.experts_down),
            *self.route(u32), u)

    def shared(self, u):
        with telemetry.device_scope("moe.shared"):
            return gated(u, *(w.astype(self.dtype) for w in (
                self.shared_gate, self.shared_up, self.shared_down)),
                limit=self.shared_limit)

    def __call__(self, u32):
        """``u32``: (..., D), the normed stream in float32.  Returns (...,
        D) in ``dtype``."""
        lead = u32.shape[:-1]
        u32 = u32.reshape(-1, u32.shape[-1]).astype(jnp.float32)
        u = u32.astype(self.dtype)
        return (self.routed_part(u, u32) + self.shared(u)).reshape(*lead, -1)
