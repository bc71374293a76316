"""Rematerialization (jax.checkpoint) for the transformer families.

``ModelConfig.remat=True`` wraps every block in ``nn.remat``: activation
memory under autodiff goes from ∝ depth to ∝ 1 block at the cost of one
extra forward per block — the standard trade that fits deep local
training on a chip.  Numerics must be EXACT: same param pytree, same
loss, same gradients.
"""

import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.fed import losses
from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.models import moe
from colearn_federated_learning_tpu.models import registry as model_registry
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)
from tests.test_nemotron_h import TINY as NEMOTRON_TINY
from tests.test_xing4 import TINY as XING_TINY


def _grads(cfg: ModelConfig, x, y):
    model = model_registry.build_model(cfg)
    params = model_registry.init_params(model, x, jax.random.PRNGKey(0))

    def loss(p):
        return losses.softmax_cross_entropy(
            model.apply({"params": p}, x, train=True), y
        )

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    return params, value, grads


def test_remat_is_numerically_identical():
    for name, x in [
        ("bert", jax.random.randint(jax.random.PRNGKey(1), (4, 64), 1, 2000)),
        ("vit_b16",
         jax.random.normal(jax.random.PRNGKey(1), (4, 28, 28, 1))),
    ]:
        y = jax.random.randint(jax.random.PRNGKey(2), (4,), 0, 4)
        base = ModelConfig(name=name, num_classes=4, width=32, depth=2,
                           num_heads=4, seq_len=64, vocab_size=2000,
                           patch_size=4)
        import dataclasses

        p0, v0, g0 = _grads(base, x, y)
        p1, v1, g1 = _grads(dataclasses.replace(base, remat=True), x, y)
        # Identical param pytree (checkpoints/wire payloads compatible).
        assert jax.tree.structure(p0) == jax.tree.structure(p1)
        np.testing.assert_allclose(float(v0), float(v1), rtol=1e-6)
        # Tight allclose, not bitwise: jax.checkpoint replays each
        # block's forward inside the backward pass, and XLA:CPU fuses /
        # reorders the recomputed reductions differently from the stored
        # activations (observed max |diff| ~3e-6 on these widths).  The
        # math is the same; the summation order is not.
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)


def test_remat_trains_in_engine():
    # Remat must not CHANGE training — so the pin is trajectory parity
    # against the non-remat engine, not a loss-goes-down heuristic (two
    # rounds of this tiny config land wherever the lr schedule takes
    # them, remat or not; both arms see the identical trajectory).
    def run(remat):
        cfg = ExperimentConfig(
            data=DataConfig(dataset="agnews_tiny", num_clients=4,
                            partition="iid", max_examples_per_client=16),
            model=ModelConfig(name="bert", num_classes=4, width=32, depth=2,
                              num_heads=4, seq_len=64, vocab_size=2000,
                              remat=remat),
            fed=FedConfig(strategy="fedavg", rounds=2, cohort_size=0,
                          local_steps=2, batch_size=4, lr=0.05, momentum=0.9),
            run=RunConfig(name="remat_test"),
        )
        return FederatedLearner(cfg).fit(rounds=2)

    hist_remat = run(True)
    hist_plain = run(False)
    assert len(hist_remat) == len(hist_plain)
    for r_rm, r_pl in zip(hist_remat, hist_plain):
        assert np.isfinite(r_rm["train_loss"])
        # Tight allclose, not exact: XLA:CPU reorders the recomputed
        # reductions under jax.checkpoint (see test above), and the ulp
        # drift compounds over local steps.
        np.testing.assert_allclose(r_rm["train_loss"], r_pl["train_loss"],
                                   rtol=1e-4)


# --- a rematerialised layer that holds a share layer (models/moe.py) --------
#
# ``remat`` in models/nemotron_h.py and models/xing4.py keeps the flash
# kernel's two results and what the share layer names
# (``SHARE_RESIDUAL_NAMES``): the rematerialised layer runs neither the
# router's product, nor the choice, nor the pairs' sort, nor the loop over the
# row tiles a second time.  "flash_only" is the policy without the share
# layer's names, the form before them.

FAMILIES = {
    # Two share layers around an attention layer; two layers of latent
    # attention and a share layer each.
    "latent": (dict(NEMOTRON_TINY, layer_pattern="E*E"), ()),
    "gated": (dict(XING_TINY, depth=2, dense_layers=0, mtp_modules=0), (1,)),
}
SHARE_LAYERS = 2
ROUTERS = ["drawn", "tied", "all_tied"]


def _names(form):
    """The policy is read where the model is traced."""
    return mock.patch.object(
        moe, "SHARE_RESIDUAL_NAMES",
        () if form == "flash_only" else moe.SHARE_RESIDUAL_NAMES)


def _share_model(family, form):
    """The model, its weights and a batch of two sequences.  ``form``:
    "kept" (``remat`` off), "remat", or "flash_only"."""
    tiny, heads = FAMILIES[family]
    model = model_registry.build_model(ModelConfig(
        **{**tiny, "remat": form != "kept"}))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 96)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 64, *heads), 0, 96)
    params = model_registry.init_params(model, ids[:1], jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: 4.0 * a if a.ndim >= 2 else a, params)
    return model, params, ids, y


def _routed(params, router):
    """``params`` with every router as drawn; with the experts in twins of
    one column, so that every score ties with another; or at 0, so that
    every score is 0.5 and ``top_k``'s way with ties is the whole choice."""
    def moved(path, a):
        if router == "drawn" or not jax.tree_util.keystr(path).endswith(
                "['router']"):
            return a
        return jnp.repeat(a[:, ::2], 2, axis=1) if router == "tied" else (
            jnp.zeros_like(a))
    return jax.tree_util.tree_map_with_path(moved, params)


def _share_grad(family, form, vmapped=False):
    """``(params, ids, y) -> (loss, gradient)`` of the form; under ``vmap``
    two clients, the second with weights and a batch of its own."""
    model, params, ids, y = _share_model(family, form)

    def grad(p, ids, y):
        return jax.value_and_grad(lambda p: losses.softmax_cross_entropy(
            model.apply({"params": p}, ids, train=True), y))(p)

    def run(*args):
        with _names(form):
            return (jax.vmap(grad) if vmapped else grad)(*args)

    return run, (params, ids, y)


def _two_clients(params, ids, y):
    return (jax.tree.map(lambda a: jnp.stack([a, 0.5 * a]), params),
            jnp.stack([ids, ids[::-1]]), jnp.stack([y, y[::-1]]))


@functools.cache
def _compiled(family, form, vmapped=False):
    run, args = _share_grad(family, form, vmapped)
    return jax.jit(run).lower(
        *(_two_clients(*args) if vmapped else args)).compile(), args


def _assert_same(got, want):
    """The loss to 1e-6 and every gradient leaf to 1e-5 of its norm: the
    recomputed reductions add in another order (see above)."""
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want[1]),
                            jax.tree.leaves(got[1])):
        gap = float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-30))
        assert gap < 1e-5, (jax.tree_util.keystr(path), gap)


@pytest.mark.parametrize("router", ROUTERS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_share_layer_names_keep_loss_and_gradients(family, router):
    """The loss and every gradient leaf with the share layer's results
    kept across rematerialisation, against ``remat`` off and against the
    policy that keeps the kernel's results alone."""
    results = {}
    for form in ("remat", "kept", "flash_only"):
        compiled, (params, ids, y) = _compiled(family, form)
        results[form] = compiled(_routed(params, router), ids, y)
    assert np.isfinite(results["remat"][0])
    _assert_same(results["remat"], results["kept"])
    _assert_same(results["remat"], results["flash_only"])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_share_layer_names_keep_loss_and_gradients_under_vmap(family):
    """Two clients under one ``vmap``, where the share layer's rules run a
    client at a time: each client's loss and gradient are its own with
    ``remat`` off and no ``vmap``."""
    mapped, args = _compiled(family, "remat", vmapped=True)
    alone, _ = _compiled(family, "kept")
    clients = _two_clients(*args)
    got = mapped(*clients)
    for i in range(2):
        _assert_same(jax.tree.map(lambda a: a[i], got),
                     alone(*jax.tree.map(lambda a: a[i], clients)))


def _rematerialised(jaxpr, inside=False, found=None):
    """Every primitive's name in a jaxpr and in the jaxprs inside it, apart
    for those inside a ``remat2`` equation and those outside any; a loop
    over row tiles (a ``while`` around grouped products) is "tile_loop"."""
    found = {True: [], False: []} if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "while" and "ragged_dot" in str(eqn.params["body_jaxpr"]):
            name = "tile_loop"
        found[inside].append(name)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [
                    value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _rematerialised(inner, inside or name == "remat2", found)
    return found


@pytest.mark.parametrize("vmapped", [False, True], ids=["jit", "vmap"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_rematerialised_share_layer_routes_and_visits_once(family,
                                                             vmapped):
    """In the gradient's jaxpr, (inside the ``remat2`` equations, outside
    them): no ``top_k`` inside, the backward's sort of the pairs and not the
    forward's, the backward's loop over the row tiles and not the forward's,
    half of what the policy without the names leaves there; the whole
    gradient chooses once a share layer, where that policy chooses twice."""
    counts = {}
    for form in ("remat", "flash_only"):
        run, args = _share_grad(family, form, vmapped)
        found = _rematerialised(jax.make_jaxpr(run)(
            *(_two_clients(*args) if vmapped else args)).jaxpr)
        counts[form] = {name: (found[True].count(name),
                               found[False].count(name))
                        for name in ("top_k", "sort", "tile_loop", "remat2")}
    layers = SHARE_LAYERS
    assert counts["flash_only"] == {
        "top_k": (layers, layers), "sort": (2 * layers, layers),
        "tile_loop": (2 * layers, layers),
        "remat2": counts["remat"]["remat2"]}
    assert counts["remat"]["remat2"][1] >= layers
    assert counts["remat"] == {
        "top_k": (0, layers), "sort": (layers, layers),
        "tile_loop": (layers, layers), "remat2": counts["remat"]["remat2"]}


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_gauge_counts_the_share_layer_names_kept(family, remat):
    gauge = lambda: telemetry.get_registry().snapshot()[  # noqa: E731
        "moe.remat_saved_arrays"]
    _share_model(family, "kept" if remat else "remat")  # set on every build
    assert gauge() == (len(moe.SHARE_RESIDUAL_NAMES) if not remat else 0)
    _share_model(family, "remat" if remat else "kept")
    assert gauge() == (len(moe.SHARE_RESIDUAL_NAMES) if remat else 0)
    assert len(moe.SHARE_RESIDUAL_NAMES) >= 5


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_outside_a_checkpoint_the_names_are_the_identity(family):
    """The evaluation's lowered text with the names and with
    ``checkpoint_name`` taken out of ``models/moe.py``: the same operations
    on the same operands in the same order.  jax lowers a primitive by way of
    a private function it then inlines, and the symbol table's counter is
    in the names of the functions that stay (``@_where_17``): those numbers
    move, and nothing else does."""
    model, params, ids, _ = _share_model(family, "remat")

    def text():
        # A new function each time: ``jit`` keeps a trace by function.
        lowered = jax.jit(
            lambda p, ids: model.apply({"params": p}, ids)).lower(params, ids)
        return re.sub(r"@([A-Za-z_]+)_\d+\b", r"@\1", lowered.as_text())

    named = text()
    with mock.patch.object(moe, "checkpoint_name", lambda a, name: a):
        assert text() == named
