"""The CNN's 64-channel stage on the view that folds two columns into the
channel axis (models/cnn.py) against the plain path: same parameters, same
logits, same gradients; engagement from the stage's shape alone."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.models import cnn
from colearn_federated_learning_tpu.models.cnn import CNN

TOL = 1e-5
GAUGE = "cnn.lane_folded_stages"


def _images(seed, n=4, h=8, w=8, c=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, h, w, c))


def _folded_stages():
    return telemetry.get_registry().snapshot().get(GAUGE)


def plain(fn, **kw):
    """``fn`` of the model on the plain path at any width: with no lanes
    to fill, no stage is half a tile."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(cnn, "LANES", 0)
        out = fn(CNN(**kw))
    assert _folded_stages() == 0
    return out


def _shapes(params):
    return {jax.tree_util.keystr(path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}


def _expected_shapes(width, in_channels=3, classes=10):
    out, cin = {}, in_channels
    for layer in range(6):
        ch = width * (1, 2, 4)[layer // 2]
        out[f"['Conv_{layer}']['bias']"] = (ch,)
        out[f"['Conv_{layer}']['kernel']"] = (3, 3, cin, ch)
        out[f"['GroupNorm_{layer}']['bias']"] = (ch,)
        out[f"['GroupNorm_{layer}']['scale']"] = (ch,)
        cin = ch
    out["['Dense_0']['bias']"] = (classes,)
    out["['Dense_0']['kernel']"] = (width * 4, classes)
    return out


def _loss(model, params, x):
    logits = model.apply({"params": params}, x)
    return jnp.sum(logits * jnp.cos(jnp.arange(logits.size, dtype=jnp.float32)
                                    ).reshape(logits.shape))


def _perturbed(params, seed):
    """Initial biases are 0 and scales 1: move every leaf so that a wrong
    tiling of either shows."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _assert_close(a, b):
    """Within ``TOL`` of the leaf's scale: what differs is the order of
    float32 additions, whose error goes with the largest term."""
    for (path, u), v in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree_util.tree_leaves(b)):
        u, v = np.asarray(u), np.asarray(v)
        np.testing.assert_allclose(
            u, v, rtol=TOL, atol=TOL * max(1.0, float(np.abs(v).max())),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("width,folded", [(8, 0), (64, 1)])
def test_parameter_tree_is_the_parents(width, folded):
    x = _images(0)
    params = CNN(width=width).init(jax.random.PRNGKey(1), x)["params"]
    assert _folded_stages() == folded
    assert _shapes(params) == _expected_shapes(width)
    reference = plain(lambda m: m.init(jax.random.PRNGKey(1), x)["params"],
                      width=width)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(reference))
    for u, v in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(reference)):
        assert u.dtype == v.dtype and np.array_equal(u, v)


@pytest.mark.parametrize("what", ["logits", "grad_params", "grad_input"])
@pytest.mark.parametrize("shape", [(8, 8), (6, 12)])
def test_folded_stage_matches_plain(what, shape):
    x = _images(2, h=shape[0], w=shape[1])
    model = CNN(width=64)
    params = _perturbed(model.init(jax.random.PRNGKey(3), x)["params"], 4)
    fn = {"logits": lambda m: m.apply({"params": params}, x),
          "grad_params": lambda m: jax.grad(
              lambda p: _loss(m, p, x))(params),
          "grad_input": lambda m: jax.grad(
              lambda v: _loss(m, params, v))(x)}[what]
    got = fn(model)
    assert _folded_stages() == 1
    _assert_close(got, plain(fn, width=64))


def test_folded_stage_matches_plain_under_vmap():
    """Two clients with their own weights and images, as the round program
    maps them."""
    x = jnp.stack([_images(5), _images(6)])
    model = CNN(width=64)
    init = model.init(jax.random.PRNGKey(7), x[0])["params"]
    params = jax.tree_util.tree_map(
        lambda a, b: jnp.stack([a, b]), _perturbed(init, 8),
        _perturbed(init, 9))

    def fn(m):
        return jax.vmap(jax.value_and_grad(
            lambda p, v: _loss(m, p, v), argnums=(0, 1)))(params, x)

    got = fn(model)
    assert _folded_stages() == 1
    _assert_close(got, plain(fn, width=64))


@pytest.mark.parametrize("kw,shape,folded", [
    ({"stem": "space_to_depth"}, (8, 8), 1),
    ({"norm": "none"}, (8, 8), 1),
    ({"stem": "space_to_depth", "norm": "none"}, (4, 4), 1),
    # one row: the stage is folded and not pooled
    ({}, (1, 4), 1),
    # an odd width takes the plain path
    ({}, (8, 9), 0),
    ({"stem": "space_to_depth"}, (6, 6), 0),
])
def test_stems_norms_and_odd_widths(kw, shape, folded):
    x = _images(10, h=shape[0], w=shape[1])
    model = CNN(width=64, **kw)
    params = _perturbed(model.init(jax.random.PRNGKey(11), x)["params"], 12)
    fn = lambda m: jax.value_and_grad(lambda p: _loss(m, p, x))(params)  # noqa: E731
    got = fn(model)
    assert _folded_stages() == folded
    assert np.isfinite(got[0])
    _assert_close(got, plain(fn, width=64, **kw))


def test_fold_kernel_blocks():
    """Six of the twelve blocks are exact zeros; the others are the
    layer's own taps."""
    kernel = jax.random.normal(jax.random.PRNGKey(13), (3, 3, 5, 7))
    folded = cnn.fold_kernel(kernel)
    assert folded.shape == (3, 3, 10, 14)
    zeros = 0
    for t in range(3):
        for s_in in range(2):
            for s_out in range(2):
                block = folded[:, t, s_in * 5:(s_in + 1) * 5,
                               s_out * 7:(s_out + 1) * 7]
                kw = 2 * (t - 1) + s_in - s_out + 1
                if 0 <= kw <= 2:
                    assert np.array_equal(block, kernel[:, kw])
                else:
                    zeros += 1
                    assert not np.any(block)
    assert zeros == 6


def _unfold(y):
    n, h, w2, c2 = y.shape
    return y.reshape(n, h, 2 * w2, c2 // 2)


LAYERS = {
    "conv": (nn.Conv(6, (3, 3), padding="SAME"), cnn.FoldedConv(6)),
    "group_norm": (nn.GroupNorm(num_groups=2),
                   cnn.FoldedGroupNorm(num_groups=2)),
}


@pytest.mark.parametrize("layer", ["conv", "group_norm", "max_pool"])
def test_folded_layer_matches_flax(layer):
    """Each folded layer alone against the flax layer it stands for, on
    the same parameters: value, and gradient by input and parameters."""
    x = jax.random.normal(jax.random.PRNGKey(16), (3, 6, 10, 4))
    weight = jnp.sin(jnp.arange(3 * 6 * 10 * 6, dtype=jnp.float32))
    if layer == "max_pool":
        params = {}
        plain_fn = lambda p, v: nn.max_pool(v, (2, 2), strides=(2, 2))  # noqa: E731
        folded_fn = lambda p, v: cnn.folded_max_pool(cnn.fold_columns(v))  # noqa: E731
    else:
        plain_m, folded_m = LAYERS[layer]
        params = _perturbed(plain_m.init(jax.random.PRNGKey(17), x), 18)
        assert _shapes(folded_m.init(jax.random.PRNGKey(17),
                                     cnn.fold_columns(x))) == _shapes(params)
        plain_fn = plain_m.apply
        folded_fn = lambda p, v: _unfold(  # noqa: E731
            folded_m.apply(p, cnn.fold_columns(v)))

    def scalar(fn):
        def f(p, v):
            y = fn(p, v)
            return jnp.sum(y * weight[:y.size].reshape(y.shape)), y
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    _assert_close(scalar(folded_fn)(params, x), scalar(plain_fn)(params, x))


def test_pool_gradient_on_ties():
    """Where a window's largest value occurs more than once, one of its
    occurrences takes the whole gradient."""
    x = jnp.asarray(np.random.default_rng(19).integers(0, 3, (2, 4, 8, 5)),
                    jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(20), (2, 2, 4, 5))
    pooled, vjp = jax.vjp(
        lambda v: cnn.folded_max_pool(cnn.fold_columns(v)), x)
    assert np.array_equal(pooled, nn.max_pool(x, (2, 2), strides=(2, 2)))
    (dx,) = vjp(g)
    windows = lambda a: np.asarray(a).reshape(2, 2, 2, 4, 2, 5)  # noqa: E731
    best = windows(x) == np.asarray(pooled)[:, :, None, :, None, :]
    taken = windows(dx) != 0
    assert not np.any(taken & ~best)
    assert np.all(taken.sum(axis=(2, 4)) <= 1)
    np.testing.assert_array_equal(windows(dx).sum(axis=(2, 4)), g)
