"""The hybrid decoder (models/nemotron_h.py): the chunked state-space scan
against the recurrence as written, the share layer of a mixture (no pair
dropped, the shares of all chips adding up to the uncut layer), fewer
key/value than query heads on the flash kernel, the model against the
benchmark's plain reference, its gauges, and ``fit()``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmarks.reference import nemotron_h as reference
from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.data import registry as data_registry
from colearn_federated_learning_tpu.fed import FederatedLearner, losses
from colearn_federated_learning_tpu.models import moe, nemotron_h, registry
from colearn_federated_learning_tpu.models.attention import MultiHeadAttention
from colearn_federated_learning_tpu.ops.attention import flash_attention
from colearn_federated_learning_tpu.ops.ssd import ssd_scan
from colearn_federated_learning_tpu.parallel.ring import dense_attention
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    RunConfig,
    get_config,
)

TINY = dict(name="nemotron_h", num_classes=96, vocab_size=96, width=32,
            seq_len=64, layer_pattern="ME*EM", mamba_heads=4,
            mamba_head_dim=8, mamba_groups=2, ssm_state_size=8, conv_kernel=4,
            chunk_size=16, num_experts=16, experts_first=4, experts_held=4,
            experts_per_token=6, latent_dim=16, expert_dim=24,
            shared_expert_dim=40, routed_scale=5.0, num_heads=4,
            num_kv_heads=2, head_dim=8, attn_impl="flash")


def _snapshot():
    return telemetry.get_registry().snapshot()


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# --- ops/ssd.py ---------------------------------------------------------------


def ssd_sequential(x, dt, a, b, c):
    """The recurrence as written, one position after another: ``S_t =
    exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``; ``ssd_scan``'s
    arguments and result, no chunk."""
    batch, length, heads, width = x.shape
    groups, state = b.shape[-2:]
    b = jnp.repeat(b, heads // groups, axis=2)            # (B, L, H, N)
    c = jnp.repeat(c, heads // groups, axis=2)

    def step(s, this):
        x_t, dt_t, b_t, c_t = this
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((batch, heads, width, state), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _scan_inputs(length, seed=0, batch=2, heads=4, width=8, groups=2, state=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (batch, length, heads, width)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, length, heads))),
            -jnp.exp(jax.random.normal(ks[2], (heads,))),
            jax.random.normal(ks[3], (batch, length, groups, state)),
            jax.random.normal(ks[4], (batch, length, groups, state)))


@pytest.mark.parametrize("length,chunk", [(64, 4), (64, 16), (64, 64),
                                          (64, 128), (50, 16), (1, 8)])
def test_chunked_scan_is_the_recurrence(length, chunk):
    """Whole chunks, one chunk, a chunk longer than the sequence, and a
    length that is no multiple of the chunk: padded with steps of ``dt =
    0`` and cut, not refused."""
    args = _scan_inputs(length)
    want = ssd_sequential(*args)
    got = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
    assert got.shape == want.shape == args[0].shape
    assert _rel(got, want) < 2e-5


def test_chunked_scan_gradients_are_the_recurrences():
    args = _scan_inputs(48, seed=1)

    def total(scan):
        return lambda *a: jnp.sum(jnp.sin(scan(*a)))

    want = jax.grad(total(ssd_sequential), argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.jit(jax.grad(total(lambda *a: ssd_scan(*a, chunk=16)),
                           argnums=(0, 1, 2, 3, 4)))(*args)
    for name, g, w in zip("x dt a b c".split(), got, want):
        assert _rel(g, w) < 5e-5, name


def test_chunked_scan_refuses_heads_that_do_not_group():
    x, dt, a, b, c = _scan_inputs(16, heads=3, groups=2)
    with pytest.raises(ValueError, match="do not divide"):
        ssd_scan(x, dt, a, b, c, chunk=8)


# --- fewer key/value than query heads -----------------------------------------


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
def test_flash_shares_key_value_heads(vmapped):
    """Query head ``h`` reads key/value head ``h // 3``: the kernel's
    answer and gradients are those of the written-out scores on repeated
    heads, and the shared heads' gradients are the copies' sums."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 24, 6, 8))
    k = jax.random.normal(ks[1], (2, 24, 2, 8))
    v = jax.random.normal(ks[2], (2, 24, 2, 8))

    def oracle(q, k, v):
        return dense_attention(q, jnp.repeat(k, 3, axis=2),
                               jnp.repeat(v, 3, axis=2), causal=True)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def total(f):
        run = lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))  # noqa: E731
        run = jax.value_and_grad(run, argnums=(0, 1, 2))
        if vmapped:
            return lambda *a: jax.vmap(run)(*(x[None] for x in a))
        return run

    (got, got_g), (want, want_g) = (jax.jit(total(f))(q, k, v)
                                    for f in (kernel, oracle))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_g, want_g):
        assert g.shape == w.shape and _rel(g, w) < 1e-5
    with pytest.raises(ValueError, match="evenly"):
        flash_attention(q[:, :, :5], k, v, causal=True)


def test_attention_module_with_fewer_key_value_heads():
    """The flash and the dense cores agree, the key and value projections
    have the fewer heads, and nothing has a bias."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    outs = {}
    for impl in ("flash", "dense"):
        layer = MultiHeadAttention(num_heads=4, num_kv_heads=2, head_dim=8,
                                   use_bias=False, causal=True, impl=impl)
        params = layer.init(jax.random.PRNGKey(1), x)["params"]
        assert jax.tree.map(jnp.shape, params) == {
            "query": {"kernel": (32, 4, 8)}, "key": {"kernel": (32, 2, 8)},
            "value": {"kernel": (32, 2, 8)}, "out": {"kernel": (4, 8, 32)}}
        outs[impl] = layer.apply({"params": params}, x)
    np.testing.assert_allclose(outs["flash"], outs["dense"], atol=1e-5)
    with pytest.raises(ValueError, match="evenly"):
        MultiHeadAttention(num_heads=4, num_kv_heads=3).init(
            jax.random.PRNGKey(1), x)


# --- the share layer ----------------------------------------------------------


def _share_layer(first=4, count=4, total=16, top_k=6, row_tile=16, **kw):
    """A block of 32 tokens has 128 rows at the bound and holds 48 pairs or
    so: several tiles of 16 are visited, and one spans two experts."""
    return moe.LatentMoEShare(
        embed_dim=32, latent_dim=16, expert_dim=24, shared_dim=40,
        experts_total=total, experts_held=(first, count), top_k=top_k,
        routed_scale=5.0, init_std=0.3, row_tile=row_tile, **kw)


def _program_and_reference(layer, first=4):
    """Value and gradients (weights, input) of a scalar of the layer's
    answer, and of the reference's loop over the held experts."""
    model = dict(experts_per_token=layer.top_k, routed_scale=5.0,
                 experts_first=first)

    def program(p, u):
        return jnp.sum(jnp.sin(layer.apply({"params": p}, u)))

    def plain(p, u):
        return jnp.sum(jnp.sin(reference.moe(u, p, model)))

    return [jax.value_and_grad(f, argnums=(0, 1)) for f in (program, plain)]


@pytest.mark.parametrize("router", ["uniform", "skewed"])
def test_no_pair_is_dropped(router):
    """The group sizes sum to the pairs that chose a held expert, every
    such pair has a row of its own expert's group, in order, and the rows
    past them weigh nothing; at a router that sends every token to all the
    held experts (the rows' static bound is then met exactly) too."""
    tokens, k, first, count = 64, 6, 4, 4
    if router == "uniform":
        scores = jax.random.uniform(jax.random.PRNGKey(0), (tokens, 16))
    else:
        scores = jnp.zeros((tokens, 16)).at[:, first:first + count].set(
            1.0 + jax.random.uniform(jax.random.PRNGKey(0), (tokens, count)))
    weights, chosen = jax.lax.top_k(scores, k)
    token, weight, held, sizes = moe.held_pairs(chosen, weights, first, count)
    chosen, token, weight, held, sizes = map(
        np.asarray, (chosen, token, weight, held, sizes))
    on_held = (chosen >= first) & (chosen < first + count)
    assert sizes.sum() == on_held.sum() == held.sum()
    assert len(token) == tokens * min(k, count)
    if router == "skewed":
        assert held.all() and (sizes == tokens).all()
    assert held[:sizes.sum()].all() and not held[sizes.sum():].any()
    assert (weight[~held] == 0).all()
    # Group g's rows are the tokens that chose expert first + g, each once.
    for g, start in enumerate(np.cumsum(sizes) - sizes):
        rows = token[start:start + sizes[g]]
        want = np.nonzero((chosen == first + g).any(axis=1))[0]
        np.testing.assert_array_equal(np.sort(rows), want)
        np.testing.assert_allclose(
            weight[start:start + sizes[g]],
            np.asarray(weights)[rows, (chosen[rows] == first + g).argmax(1)])


@pytest.mark.parametrize("vmapped", [False, True], ids=["jit", "vmap"])
def test_share_layer_is_the_plain_loop_over_held_experts(vmapped):
    """Answer and every gradient leaf against the reference's loop, with a
    client axis in front too (``fed/programs.py`` maps it so); tokens in
    two blocks."""
    layer = _share_layer(token_block=32)
    u = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    run = _program_and_reference(layer)
    args = (params, u)
    if vmapped:
        run = [jax.vmap(f) for f in run]
        args = (jax.tree.map(lambda a: jnp.stack([a, 0.5 * a]), params),
                jnp.stack([u, u[::-1]]))
    (got, got_g), (want, want_g) = (jax.jit(f)(*args) for f in run)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            # It enters the choice alone.
            assert not np.asarray(g).any() and not np.asarray(w).any()
        else:
            assert _rel(g, w) < 2e-5, name


def test_tile_sizes_cut_the_groups_at_the_tiles_edges():
    """A tile may span several groups and a group several tiles; past the
    groups' end a tile holds nothing."""
    sizes = jnp.array([3, 0, 6, 2])
    got = [moe.tile_sizes(sizes, start, 4).tolist() for start in (0, 4, 8, 12)]
    assert got == [[3, 0, 1, 0], [0, 0, 4, 0], [0, 0, 1, 2], [0, 0, 0, 0]]


def _assert_close_or_both_zero(got, want, name):
    if np.asarray(want).any():
        assert _rel(got, want) < 2e-5, name
    else:
        assert not np.asarray(got).any(), name


# The correction bias on the held experts: 0 leaves the router as drawn, +10
# sends every token to all of them (the rows' static bound is met), -10 none.
@pytest.mark.parametrize("biases", [(0.0,), (10.0,), (-10.0,), (10.0, 0.0),
                                    (0.0, -10.0)],
                         ids=["uniform", "every_held", "none_held",
                              "vmap_bound_and_uniform",
                              "vmap_uniform_and_none"])
def test_row_tiles_visited_follow_the_routing(biases):
    """The loop visits ``ceil(held pairs / tile)`` tiles of a block's 8: a
    few under the router as drawn (one of them across two experts), all at
    the bound, none where no pair is held (the answer is 0 there, and every
    gradient 0 and finite).  Whatever it visits, answer and every gradient
    leaf are the reference's loop over the held experts, for two clients
    with different counts under one ``vmap`` too."""
    tile, first, count = 16, 4, 4
    layer = _share_layer(token_block=32, row_tile=tile)
    us = jax.random.normal(jax.random.PRNGKey(0), (len(biases), 64, 32))
    drawn = layer.init(jax.random.PRNGKey(1), us[0])["params"]
    clients = [dict(drawn, router_bias=drawn["router_bias"].at[
        first:first + count].set(bias)) for bias in biases]
    for bias, params, u in zip(biases, clients, us):
        chosen, weights = layer.apply({"params": params}, u, method="route")
        latent = (u @ params["latent_down"]).reshape(2, 32, -1)
        token, weight, held, sizes = jax.vmap(
            lambda c, w: moe.held_pairs(c, w, first, count))(
            chosen.reshape(2, 32, -1), weights.reshape(2, 32, -1))
        out, visited = jax.jit(moe.visit_row_tiles, static_argnums=0)(
            tile, latent, weight, params["experts_w1"], params["experts_w2"],
            token, held, sizes)
        pairs = np.asarray(sizes.sum(axis=1))
        np.testing.assert_array_equal(visited, -(-pairs // tile))
        if bias > 0:
            assert (pairs == token.shape[1]).all() and (visited == 8).all()
        elif bias < 0:
            assert not pairs.any() and not np.asarray(out).any()
        else:
            assert (1 < visited).all() and (visited < 8).all()
            assert np.count_nonzero(moe.tile_sizes(sizes[0], 0, tile)) > 1
    run = _program_and_reference(layer, first)
    if len(biases) == 1:
        args = (clients[0], us[0])
    else:
        run = [jax.vmap(f) for f in run]
        args = (jax.tree.map(lambda *a: jnp.stack(a), *clients), us)
    (got, got_g), (want, want_g) = (jax.jit(f)(*args) for f in run)
    # A sum of 2,048 sines that may cancel: float32 leaves it 1e-4 or so.
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-4)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        name = jax.tree_util.keystr(path)
        assert np.isfinite(g).all(), name
        if "router_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any()
        elif len(biases) == 1:
            _assert_close_or_both_zero(g, w, name)
            if biases[0] < 0 and ("experts" in name or name == "['router']"):
                assert not np.asarray(g).any(), name
        else:
            for client in range(len(biases)):
                _assert_close_or_both_zero(g[client], w[client], name)


def test_share_layer_refuses_experts_it_cannot_hold():
    u = jnp.zeros((8, 32))
    with pytest.raises(ValueError, match="no range"):
        _share_layer(first=14, count=4).init(jax.random.PRNGKey(0), u)
    with pytest.raises(ValueError, match="whole blocks"):
        _share_layer(token_block=3).init(jax.random.PRNGKey(0), u)


# --- the routing, against the forms it replaced ------------------------------
#
# What ``route`` and ``held_pairs`` computed until PR 36, as the plain
# statement of what they give: a gather of the chosen scores, and one sort of
# every (token, choice) pair with the pairs' vectors gathered in its order.


def picked_by_gather(scores, chosen):
    return jnp.take_along_axis(scores, chosen, axis=-1)


def held_pairs_by_pair_sort(chosen, weights, first, count):
    tokens, k = chosen.shape
    bound = tokens * min(k, count)
    local = jnp.where((chosen >= first) & (chosen < first + count),
                      chosen - first, count).reshape(-1)
    pairs = tokens * k
    order = jnp.sort(local * pairs + jnp.arange(pairs))[:bound] % pairs
    held = local[order] < count
    sizes = jnp.sum(local[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return (order // k, jnp.where(held, weights.reshape(-1)[order], 0.0),
            held, sizes)


def the_forms_replaced(monkeypatch):
    monkeypatch.setattr(moe, "picked_scores", picked_by_gather)
    monkeypatch.setattr(moe, "held_pairs", held_pairs_by_pair_sort)


# Experts, choices a token, the held range: both share layers' (22 of 512 and
# 4 of 64, 8 held), and fewer and more choices than held experts.
ROUTERS = {"latent_22_of_512": (512, 22, 40, 8),
           "gated_4_of_64": (64, 4, 8, 8), "choices_below_held": (16, 2, 4, 4),
           "choices_above_held": (16, 6, 4, 4), "all_held": (8, 3, 0, 8)}
ROUTINGS = ["drawn", "none_held", "every_held", "tied", "all_tied"]


def _routing(router, routing, tokens=96):
    """Scores (T, E) and a correction bias (E,): as drawn; the held experts
    out of every token's reach, or in front of every token's choice (the
    rows' bound is met); scores of three values, or of one, so that
    ``top_k``'s way with ties is what chooses."""
    total, k, first, count = ROUTERS[router]
    scores = jax.nn.sigmoid(
        jax.random.normal(jax.random.PRNGKey(3), (tokens, total)))
    bias = jnp.zeros((total,))
    if routing == "none_held" and count < total:
        bias = bias.at[first:first + count].set(-10.0)
    elif routing == "every_held":
        bias = bias.at[first:first + count].set(10.0)
    elif routing == "tied":
        scores = jnp.round(2 * scores) / 2
    elif routing == "all_tied":
        scores = jnp.full_like(scores, 0.5)
    return scores, bias, k, first, count


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_routing_is_the_gather_and_the_pair_sort(router, routing):
    """The chosen scores to the last bit; the rows' weights to the last
    bit, ``held`` and ``sizes`` exactly, and the held rows' tokens in the
    pair sort's order (by expert, then by token); rows past the held pairs
    weigh 0 and name a token of the block.  Under a client axis too."""
    scores, bias, k, first, count = _routing(router, routing)
    _, chosen = jax.lax.top_k(scores + bias, k)
    picked = jax.jit(moe.picked_scores)(scores, chosen)
    np.testing.assert_array_equal(picked, picked_by_gather(scores, chosen))
    weights = 5.0 * picked / picked.sum(-1, keepdims=True)
    want = held_pairs_by_pair_sort(chosen, weights, first, count)
    got = jax.jit(moe.held_pairs, static_argnums=(2, 3))(
        chosen, weights, first, count)
    mapped = jax.vmap(lambda c, w: moe.held_pairs(c, w, first, count))(
        jnp.stack([chosen, chosen[::-1]]), jnp.stack([weights, weights[::-1]]))
    for (token, weight, held, sizes) in (got, [a[0] for a in mapped]):
        np.testing.assert_array_equal(held, want[2])
        np.testing.assert_array_equal(sizes, want[3])
        np.testing.assert_array_equal(weight, want[1])
        np.testing.assert_array_equal(token[want[2]], want[0][want[2]])
        assert ((0 <= token) & (token < len(chosen))).all()
    pairs, rows = int(want[3].sum()), len(want[0])
    assert rows == len(chosen) * min(k, count)
    if routing == "none_held" and count < ROUTERS[router][0]:
        assert pairs == 0
    if routing == "every_held":
        assert pairs == rows
    if routing == "all_tied":
        # The lowest experts win every tie.
        np.testing.assert_array_equal(chosen[0], np.arange(k))


def _primitives(jaxpr, found=None):
    """Every primitive's name in a jaxpr and in the jaxprs inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        for value in eqn.params.values():
            inside = value if isinstance(value, (list, tuple)) else [value]
            for inner in inside:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


def _element_by_element(names):
    return sorted(n for n in names if "gather" in n or "scatter" in n
                  or n in ("dynamic_slice", "dynamic_update_slice"))


@pytest.mark.parametrize("router", ["latent_22_of_512", "gated_4_of_64"])
def test_routing_gathers_and_scatters_no_scalar(router):
    """No gather, no scatter and no slice at a traced index in ``route``'s
    chosen scores, in ``held_pairs`` or in their pullbacks: comparisons,
    selects, sums, and one sort of ``count * T`` keys forward and one
    backward.  The forms replaced have them, and the count finds them."""
    scores, bias, k, first, count = _routing(router, "drawn")
    _, chosen = jax.lax.top_k(scores + bias, k)
    weights = picked_by_gather(scores, chosen)

    def pulled_back(f, x):
        def run(x, g):
            out, pull = jax.vjp(f, x)
            return out, pull(g)
        return _primitives(jax.make_jaxpr(run)(x, f(x)).jaxpr)

    def rows_weight(held_pairs):
        return lambda w: held_pairs(chosen, w, first, count)[1]

    new = (pulled_back(lambda s: moe.picked_scores(s, chosen), scores),
           pulled_back(rows_weight(moe.held_pairs), weights),
           _primitives(jax.make_jaxpr(
               lambda c, w: moe.held_pairs(c, w, first, count))(
               chosen, weights).jaxpr))
    for names in new:
        assert not _element_by_element(names), names
    assert [new[i].count("sort") for i in (0, 1, 2)] == [0, 2, 1]
    old = (pulled_back(lambda s: picked_by_gather(s, chosen), scores),
           pulled_back(rows_weight(held_pairs_by_pair_sort), weights))
    for names in old:
        found = _element_by_element(names)
        assert "gather" in found and any("scatter" in n for n in found)
    # The keys of a block's sort, as the gauge reports them.
    assert moe.pair_sort_keys(len(chosen), count) == count * len(chosen)


def assert_as_with_the_forms_replaced(layer, first, count, biases,
                                      monkeypatch):
    """A share layer's choice exactly, ``route``'s weights within 4 ulp of
    the gather's (the chosen scores are the gather's to the bit; XLA folds
    their sum into the same pass, so the denominator adds in another
    order, 2 ulp, and the quotient rounds around it), and a scalar of the
    layer's answer with its gradient in every weight and in the input as
    with the forms replaced: the same rows in the same order meet the same
    tiles.  ``biases``: the correction bias on the held experts, one value a
    client; several clients go under one ``vmap``."""
    us = jax.random.normal(jax.random.PRNGKey(0), (len(biases), 64, 32))
    drawn = layer.init(jax.random.PRNGKey(1), us[0])["params"]
    clients = [dict(drawn, router_bias=drawn["router_bias"].at[
        first:first + count].set(bias)) for bias in biases]

    def run():
        def scalar(p, u):
            return jnp.sum(jnp.sin(layer.apply({"params": p}, u)))
        grad = jax.value_and_grad(scalar, argnums=(0, 1))
        route = lambda p, u: layer.apply({"params": p}, u, method="route")
        if len(biases) == 1:
            args = (clients[0], us[0])
        else:
            grad, route = jax.vmap(grad), jax.vmap(route)
            args = (jax.tree.map(lambda *a: jnp.stack(a), *clients), us)
        return jax.jit(route)(*args), jax.jit(grad)(*args)

    (chosen, weights), (got, got_g) = run()
    the_forms_replaced(monkeypatch)
    (chosen_was, weights_was), (want, want_g) = run()
    np.testing.assert_array_equal(chosen, chosen_was)
    np.testing.assert_array_max_ulp(weights, weights_was, maxulp=4)
    # A sum of 2,048 sines that may cancel.
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=5e-4)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        name = jax.tree_util.keystr(path)
        assert np.isfinite(g).all(), name
        if np.asarray(w).any():
            # The weights' few ulp, through a sum over the tokens.
            assert _rel(g, w) < 1e-5, name
        else:
            assert not np.asarray(g).any(), name


@pytest.mark.parametrize("biases", [(0.0,), (10.0,), (-10.0,), (10.0, 0.0)],
                         ids=["uniform", "every_held", "none_held",
                              "vmap_bound_and_uniform"])
def test_share_layer_gradients_are_the_replaced_forms(biases, monkeypatch):
    """The latent layer's routing, answer and gradient in the router, the
    latent maps, the banks, the shared expert and the input, against the
    gather of the chosen scores and the sort of every pair."""
    assert_as_with_the_forms_replaced(
        _share_layer(token_block=32), 4, 4, biases, monkeypatch)


UNCUT = dict(width=32, mamba_heads=8, mamba_head_dim=4, mamba_groups=4,
             ssm_state_size=8, conv_kernel=4, chunk_size=16, num_experts=128,
             experts_first=0, experts_held=128, experts_per_token=6,
             routed_scale=5.0, latent_dim=16, expert_dim=24,
             shared_expert_dim=40, num_heads=8, num_kv_heads=2, head_dim=4)


def _uncut_params(kind, key):
    """Seeded weights of one uncut layer under the reference's names."""
    sizes = {
        "mamba": {"in_proj": {"kernel": (32, 2 * 32 + 2 * 32 + 8)},
                  "conv_kernel": (4, 32 + 2 * 32), "conv_bias": (96,),
                  "dt_bias": (8,), "A_log": (8,), "D": (8,), "norm": (32,),
                  "out_proj": {"kernel": (32, 32)}},
        "moe": {"router": (32, 128), "router_bias": (128,),
                "latent_down": (32, 16), "latent_up": (16, 32),
                "experts_w1": (128, 16, 24), "experts_w2": (128, 24, 16),
                "shared_w1": (32, 40), "shared_w2": (40, 32)},
        "attention": {"query": {"kernel": (32, 8, 4)},
                      "key": {"kernel": (32, 2, 4)},
                      "value": {"kernel": (32, 2, 4)},
                      "out": {"kernel": (8, 4, 32)}},
    }[kind]
    leaves, tree = jax.tree.flatten(sizes, is_leaf=lambda s: isinstance(
        s, tuple))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        0.4 * jax.random.normal(k, s) for k, s in zip(keys, leaves)])


def _mamba_share(p, j, shares=4):
    """Share ``j``'s heads and groups of an uncut Mamba layer: the columns
    of ``in_proj`` are ``[z | x | B | C | dt]``."""
    heads, width, groups, state = 8, 4, 4, 8
    inner, bc = heads * width, groups * state
    h, g = heads // shares, groups // shares

    def cols(start, part):
        return np.arange(start + j * part, start + (j + 1) * part)

    z = cols(0, h * width)
    x = cols(inner, h * width)
    b = cols(2 * inner, g * state)
    c = cols(2 * inner + bc, g * state)
    dt = cols(2 * inner + 2 * bc, h)
    conv = np.concatenate([x, b, c]) - inner
    own = slice(j * h, (j + 1) * h)
    return {
        "in_proj": {"kernel": p["in_proj"]["kernel"][
            :, np.concatenate([z, x, b, c, dt])]},
        "conv_kernel": p["conv_kernel"][:, conv],
        "conv_bias": p["conv_bias"][conv],
        "dt_bias": p["dt_bias"][own], "A_log": p["A_log"][own],
        "D": p["D"][own], "norm": p["norm"][z],
        "out_proj": {"kernel": p["out_proj"]["kernel"][z]},
    }


@pytest.mark.parametrize("kind", ["mamba", "moe", "attention"])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(kind):
    """The program's layer, built as one chip's share and given that
    share's slice of an uncut layer's weights, once for every share: 4
    head shares of a Mamba layer (2 of 8 heads with 1 of 4 groups each)
    and of an attention layer (2 of 8 query heads on the 1 of 2 key/value
    heads they read); 64 expert shares of a mixture of 128 (2 each), where
    what every chip computes alike (the router, the shared expert) counts
    once and the latent up-projection is applied once to the summed latent
    parts.  The sum is the uncut reference's layer."""
    u = jax.random.normal(jax.random.PRNGKey(0), (48, 32))
    p = _uncut_params(kind, jax.random.PRNGKey(1))
    want = reference.MIXERS[kind](u, p, UNCUT)
    if kind == "mamba":
        layer = nemotron_h.Mamba2Mixer(
            num_heads=2, head_dim=4, n_groups=1, state_size=8, conv_kernel=4,
            chunk=16)
        got = sum(layer.apply({"params": _mamba_share(p, j)}, u[None])[0]
                  for j in range(4))
    elif kind == "attention":
        layer = MultiHeadAttention(num_heads=2, num_kv_heads=1, head_dim=4,
                                   use_bias=False, causal=True, impl="flash")
        got = sum(layer.apply({"params": {
            "query": {"kernel": p["query"]["kernel"][:, 2 * j:2 * j + 2]},
            "key": {"kernel": p["key"]["kernel"][:, j // 2:j // 2 + 1]},
            "value": {"kernel": p["value"]["kernel"][:, j // 2:j // 2 + 1]},
            "out": {"kernel": p["out"]["kernel"][2 * j:2 * j + 2]},
        }}, u[None])[0] for j in range(4))
    else:
        parts, pairs = [], 0
        for j in range(64):
            layer = _share_layer(first=2 * j, count=2, total=128)
            own = dict(p, experts_w1=p["experts_w1"][2 * j:2 * j + 2],
                       experts_w2=p["experts_w2"][2 * j:2 * j + 2])
            parts.append(layer.apply(
                {"params": own}, u, u, method="routed_latent"))
            chosen, weights = layer.apply({"params": own}, u, method="route")
            pairs += int(moe.held_pairs(chosen, weights, 2 * j, 2)[3].sum())
        assert pairs == 48 * 6          # every pair on exactly one share
        shared = layer.apply({"params": own}, u, method="shared")
        got = sum(parts) @ p["latent_up"] + shared
    assert _rel(got, want) < 1e-5


# --- the model ----------------------------------------------------------------


def _model_and_batch(remat=False, **changes):
    model = registry.build_model(ModelConfig(
        **{**TINY, "remat": remat, **changes}))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 96)
    params = registry.init_params(model, ids[:1], jax.random.PRNGKey(0))
    # Enlarged, so that the mixers weigh against the embedding and the
    # softmaxes are not flat at this width.
    params = jax.tree.map(lambda a: 4.0 * a if a.ndim >= 2 else a, params)
    return model, params, ids, y


def _loss_fn(model, ids, y):
    return lambda p: losses.softmax_cross_entropy(
        model.apply({"params": p}, ids, train=True), y)


@pytest.mark.parametrize("vmapped", [False, True], ids=["jit", "vmap"])
@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_model_matches_the_plain_reference(remat, vmapped):
    """The loss and every gradient leaf, float32 against float32."""
    model, params, ids, y = _model_and_batch(remat=remat)

    def program(p, ids, y):
        return jax.value_and_grad(_loss_fn(model, ids, y))(p)

    def plain(p, ids, y):
        return jax.value_and_grad(
            lambda p: reference.loss(p, ids, y, TINY))(p)

    args = (params, ids, y)
    if vmapped:
        program, plain = jax.vmap(program), jax.vmap(plain)
        args = (jax.tree.map(lambda a: jnp.stack([a, 0.5 * a]), params),
                jnp.stack([ids, ids[::-1]]), jnp.stack([y, y[::-1]]))
    (loss, grads), (ref_loss, ref_grads) = (
        jax.jit(f)(*args) for f in (program, plain))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for (path, want), got in zip(
            jax.tree_util.tree_leaves_with_path(ref_grads),
            jax.tree.leaves(grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(got).any(), name
        else:
            assert _rel(got, want) < 2e-5, name


def test_model_logits_and_the_references():
    model, params, ids, _ = _model_and_batch()
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 64, 96) and logits.dtype == jnp.float32
    np.testing.assert_allclose(
        logits, reference.forward(params, ids, TINY), atol=2e-5)


@pytest.mark.parametrize("position", [15, 16, 17, 40])
def test_model_is_causal(position):
    """A change at a position leaves the logits before it as they were:
    across a chunk's boundary (16) and inside one."""
    model, params, ids, _ = _model_and_batch()
    before = model.apply({"params": params}, ids)
    changed = ids.at[:, position].set((ids[:, position] + 1) % 96)
    after = model.apply({"params": params}, changed)
    np.testing.assert_allclose(before[:, :position], after[:, :position],
                               atol=1e-6)
    assert not np.allclose(before[:, position:], after[:, position:])


def test_gauges_say_what_was_built():
    _model_and_batch(layer_pattern="M")       # set on every build
    _model_and_batch()
    got = _snapshot()
    assert got["hybrid.layers{kind=mamba}"] == 2
    assert got["hybrid.layers{kind=moe}"] == 2
    assert got["hybrid.layers{kind=attention}"] == 1
    assert (got["ssd.heads"], got["ssd.chunk"], got["ssd.state"]) == (4, 16, 8)
    assert (got["moe.experts_held"], got["moe.experts_total"],
            got["moe.top_k"]) == (4, 16, 6)
    # One sequence of 64 tokens, at most 4 held experts a token; the
    # tile is the module's own, cut to the rows a block has.
    assert got["moe.dispatch_rows"] == got["moe.row_tile"] == 64 * 4
    # A block's sort: its membership table, 4 held experts x 64 tokens.
    assert got["moe.pair_sort_keys"] == 4 * 64
    _model_and_batch(layer_pattern="M*")
    assert _snapshot()["hybrid.layers{kind=moe}"] == 0


def test_registry_guards_name_the_family():
    with pytest.raises(ValueError, match="a layer is one of"):
        _model_and_batch(layer_pattern="MXE")
    with pytest.raises(ValueError, match="not 'ring'"):
        registry.build_model(ModelConfig(**{**TINY, "attn_impl": "ring"}))
    with pytest.raises(ValueError, match="not 'nemotron_h'"):
        registry.build_model(ModelConfig(**TINY), seq_axis_name="seq")
    with pytest.raises(ValueError, match="nemotron_h"):
        registry.build_model(ModelConfig(name="mlp", remat=True))
    shipped = get_config("nemotron_h_fedavg").model
    assert shipped.remat and shipped.layer_pattern == "MEMEMEM*EME"
    assert registry.build_model(shipped).experts_held == (0, 8)


def test_tokens_dataset_labels_every_position():
    data = data_registry.get_dataset("tokens_tiny", seed=3)
    x, y = data.x_train, data.y_train
    assert x.shape == y.shape == (64, 64) and data.y_test.shape == (8, 64)
    assert x.dtype == y.dtype == np.int32
    np.testing.assert_array_equal(y[:, :-1], x[:, 1:])
    assert 0 <= x.min() and x.max() < 96 and (x == 0).any()
    again = data_registry.get_dataset("tokens_tiny", seed=3)
    np.testing.assert_array_equal(again.x_train, x)
    assert (data_registry.get_dataset("tokens_tiny", seed=4).x_train != x).any()
    # Heavy-tailed: the commonest words are far above a uniform share.
    counts = np.bincount(x.ravel(), minlength=96)
    assert counts[1:9].sum() > 3 * counts[-8:].sum()


def _experiment(cohort, **model):
    shipped = get_config("nemotron_h_fedavg")
    assert shipped.model.width == 4096 and shipped.model.num_experts == 512
    return ExperimentConfig(
        data=DataConfig(dataset="tokens_tiny", num_clients=4,
                        partition="iid"),
        model=dataclasses.replace(
            shipped.model, **{**TINY, "dtype": "float32", **model}),
        fed=dataclasses.replace(shipped.fed, cohort_size=cohort, lr=0.1),
        run=RunConfig(name="nemotron_h_tiny", eval_every=1))


@pytest.mark.parametrize("devices", [1, 2], ids=["vmap", "mesh2"])
def test_fit_trains_and_evaluates(devices):
    """Three rounds through ``fit()`` with an evaluation after each: the
    loss falls, one round program was built, the share layer and the scan
    were (their gauges say so) and the working set of rows stayed off."""
    before = _snapshot()
    mesh = None if devices == 1 else Mesh(
        np.array(jax.devices()[:devices]), ("clients",))
    learner = FederatedLearner(_experiment(cohort=devices), mesh=mesh)
    records = learner.fit(rounds=3)
    assert len(records) == 3
    assert all(np.isfinite(r["train_loss"]) for r in records)
    assert records[0]["train_loss"] == pytest.approx(np.log(96), rel=0.04)
    assert records[-1]["eval_loss"] < records[0]["eval_loss"] < np.log(96)
    assert records[-1]["train_loss"] < records[0]["train_loss"]
    loss, acc = learner.evaluate()
    assert loss == pytest.approx(records[-1]["eval_loss"])
    assert 0.0 <= acc <= 1.0
    assert learner._round_fn.compiles == 1
    after = _snapshot()
    assert after["moe.experts_held"] == 4 and after["ssd.chunk"] == 16
    assert after["hybrid.layers{kind=attention}"] == 1
    assert after.get("local.compact_tables", 0) == before.get(
        "local.compact_tables", 0)


def test_dense_fit_agrees_with_flash():
    flash = FederatedLearner(_experiment(cohort=1)).fit(rounds=2)
    dense = FederatedLearner(_experiment(cohort=1, attn_impl="dense")).fit(
        rounds=2)
    for a, b in zip(flash, dense):
        assert a["train_loss"] == pytest.approx(b["train_loss"], rel=1e-4)
        assert a["eval_loss"] == pytest.approx(b["eval_loss"], rel=1e-4)
