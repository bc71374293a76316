"""Loss and metric functions (float32 accumulation regardless of model dtype)."""

from __future__ import annotations

import jax.numpy as jnp
import optax

from colearn_federated_learning_tpu import telemetry


def softmax_cross_entropy(logits, labels) -> jnp.ndarray:
    """Mean cross-entropy over every label.  ``logits`` (..., K), ``labels``
    (...) int with the same leading shape: (B, K) against (B,) for a class
    per example, (B, L, P, K) against (B, L, P) for a label per token and
    prediction head (models/evabyte.py), where the mean is over examples,
    positions and heads alike."""
    if logits.shape[:-1] != labels.shape:
        raise ValueError(
            f"logits {logits.shape} need labels {logits.shape[:-1]}, "
            f"got {labels.shape}")
    # With the model's last norm and its logits: the ``head`` of the
    # device trace.
    with telemetry.device_scope("head"):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        ).mean()


def accuracy(logits, labels) -> jnp.ndarray:
    """Share of the labels (of any leading shape) the argmax hits."""
    return (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32).mean()
