"""Parity probe: the program's model and loss against the family's plain
reference, on the cell's device, at the cell's full widths.

The learner's own seeded initial weights and one seeded batch go through
(i) ``learner.model`` and the program's loss, as the cell configures them
(bf16 activations in both configurations), and (ii)
``benchmarks/reference/<family>.py`` in float32 under
``default_matmul_precision("highest")``.  Compared are the loss and, leaf
by leaf, the gradient: ``|g - g_ref| / (|g_ref| + floor * |g_ref| over all
leaves)``.  The floor is there because some leaves have a gradient that is
zero in exact arithmetic (an attention key bias shifts every score of a
row alike; a bias in front of a one-channel GroupNorm group) and rounding
noise on both sides.  The tolerances are the family's, written with their
reasons in its reference file.

Runs before the warm-up round: the round program donates the weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def parity(learner, reference, config_doc: dict) -> dict:
    """Returns the measured gaps and ``ok``.  One batch of the cell's own
    batch size, the first rows of the seeded training split."""
    from colearn_federated_learning_tpu.fed import losses

    n = config_doc["experiment"]["fed"]["batch_size"]
    model = config_doc["experiment"]["model"]

    def program_loss(params, x, y):
        logits = learner.model.apply({"params": params}, x, train=True)
        return losses.softmax_cross_entropy(logits, y)

    def reference_loss(params, x, y):
        return reference.loss(params, x, y, model)

    # One program for both sides, which hands back two losses and two
    # numbers per leaf: the gradients of a BERT-base are 0.9 GB that every
    # run would otherwise bring to the host.  The batch is an argument: a
    # program that held it as a constant would be compiled anew for every
    # seed.
    @jax.jit
    def both(params, x, y):
        loss, grads = jax.value_and_grad(program_loss)(params, x, y)
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_grads = jax.value_and_grad(reference_loss)(
                params, x, y)
        square = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)))  # noqa: E731
        return (loss, ref_loss,
                jax.tree.map(lambda g, r: square(g - r), grads, ref_grads),
                jax.tree.map(square, ref_grads))

    loss, ref_loss, gap_sq, ref_sq = jax.device_get(both(
        learner.params, jnp.asarray(learner.dataset.x_train[:n]),
        jnp.asarray(learner.dataset.y_train[:n])))
    return {**compare(float(loss), float(ref_loss), gap_sq, ref_sq,
                      reference.TOLERANCE), "batch": n}


def compare(loss: float, ref_loss: float, gap_sq, ref_sq,
            tolerance: dict) -> dict:
    """``gap_sq``/``ref_sq``: per leaf, the squared norm of ``g - g_ref``
    and of ``g_ref``.  ``tolerance``: ``loss`` (relative), ``grad_leaf``
    and ``grad_floor`` as in the module's docstring."""
    total = math.sqrt(sum(float(r) for r in jax.tree.leaves(ref_sq)))
    worst, worst_leaf = 0.0, ""
    for (path, gap), ref in zip(jax.tree_util.tree_leaves_with_path(gap_sq),
                                jax.tree.leaves(ref_sq)):
        rel = math.sqrt(float(gap)) / (
            math.sqrt(float(ref)) + tolerance["grad_floor"] * total)
        rel = math.inf if math.isnan(rel) else rel
        if rel > worst:
            worst, worst_leaf = rel, jax.tree_util.keystr(path)
    loss_gap = abs(loss - ref_loss) / abs(ref_loss)
    return {
        "loss": loss, "ref_loss": ref_loss, "loss_rel_gap": loss_gap,
        "grad_rel_gap_max": worst, "grad_worst_leaf": worst_leaf,
        "ok": bool(loss_gap <= tolerance["loss"]
                   and worst <= tolerance["grad_leaf"]),
    }
