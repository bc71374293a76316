"""Held-out evaluation (SURVEY.md §3d evaluator role), standalone.

One jit-compiled scan over padded test batches — shared by the engine's
periodic eval and the file-based evaluator (`colearn eval`), which needs no
training setup at all.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

# An evaluation batch: this many rows, or the trainer's batch if that is
# larger; rows of token ids, whose cost grows with their length, as many as
# hold this many tokens (a 16,384-token sequence is a batch of its own).
EVAL_ROWS = 64
EVAL_TOKENS = 16_384


def eval_rows(batch_size: int, x) -> int:
    """Rows of ``x`` (the examples, first axis the rows) in one batch of an
    evaluation program."""
    rows = max(batch_size, EVAL_ROWS)
    if np.issubdtype(x.dtype, np.integer):
        tokens = math.prod(x.shape[1:])
        rows = min(rows, max(batch_size, EVAL_TOKENS // tokens))
    return rows


def per_label(mask, like):
    """A per-row ``mask`` (..., rows) as a weight for each label of
    ``like`` (..., rows, *labels of a row)."""
    return jnp.broadcast_to(
        mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim)), like.shape)


def _pad_batches(x_test, y_test, batch: int):
    """(xb, yb, mb) device arrays: the test set padded to whole
    ``batch``-sized chunks with a validity mask — static shapes, shared
    by every eval builder in this module.  ``y_test`` holds a class per
    row, (n,), or any block of labels per row, (n, ...)."""
    x_test = np.asarray(x_test)
    y_test = np.asarray(y_test)
    n = len(x_test)
    n_batches = int(np.ceil(n / batch))
    pad = n_batches * batch - n
    x_pad = np.concatenate(
        [x_test, np.zeros((pad,) + x_test.shape[1:], x_test.dtype)])
    y_pad = np.concatenate(
        [y_test, np.zeros((pad,) + y_test.shape[1:], y_test.dtype)])
    mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    xb = jnp.asarray(x_pad.reshape((n_batches, batch) + x_test.shape[1:]))
    yb = jnp.asarray(y_pad.reshape((n_batches, batch) + y_test.shape[1:]))
    mb = jnp.asarray(mask.reshape((n_batches, batch)))
    return xb, yb, mb


def make_eval_fn(apply_fn: Callable, x_test, y_test, batch: int) -> Callable:
    """Build ``eval_fn(params) -> (mean_loss, accuracy)`` over the test set,
    reduced in a single ``lax.scan`` — one compile.  The mean and the share
    are over labels: one a row, or every token's (``y_test`` of (n, ...)
    against logits of (n, ..., K))."""
    xb, yb, mb = _pad_batches(x_test, y_test, batch)

    @jax.jit
    def eval_fn(params):
        def step(carry, inp):
            x, y, m = inp
            logits = apply_fn({"params": params}, x, train=False)
            ce = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(ce, y[..., None], axis=-1)[..., 0]
            correct = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
            m = per_label(m, nll)
            loss_sum, acc_sum, m_sum = carry
            return (
                loss_sum + jnp.sum(nll * m),
                acc_sum + jnp.sum(correct * m),
                m_sum + jnp.sum(m),
            ), None

        (loss_sum, acc_sum, m_sum), _ = jax.lax.scan(
            step, (0.0, 0.0, 0.0), (xb, yb, mb)
        )
        return loss_sum / m_sum, acc_sum / m_sum

    return eval_fn


def make_confusion_eval_fn(apply_fn: Callable, x_test, y_test, batch: int,
                           num_classes: int) -> Callable:
    """Build ``fn(params) -> (C, C) confusion matrix`` (rows = true class,
    cols = prediction) over the test set — same padded-scan structure as
    :func:`make_eval_fn`, accumulating one scatter-add per batch."""
    xb, yb, mb = _pad_batches(x_test, y_test, batch)
    C = num_classes

    @jax.jit
    def conf_fn(params):
        def step(conf, inp):
            x, y, m = inp
            logits = apply_fn({"params": params}, x, train=False)
            pred = jnp.argmax(logits, axis=-1)
            flat = y.astype(jnp.int32) * C + pred.astype(jnp.int32)
            return conf.at[flat].add(per_label(m, flat)), None

        conf, _ = jax.lax.scan(step, jnp.zeros(C * C, jnp.float32),
                               (xb, yb, mb))
        return conf.reshape(C, C)

    return conf_fn


def detection_report(conf: np.ndarray, benign_class: int = 0) -> dict:
    """Detection-oriented metrics from a confusion matrix — the quantities
    the reference's IoT network-anomaly deployment actually cares about
    (SURVEY.md §0: MUD-compliant edge anomaly detection), where plain
    accuracy hides a useless always-benign classifier:

    - per-class precision/recall/F1 + macro-F1;
    - binary ALARM view (any non-benign prediction is an alarm):
      ``detection_rate`` = P(alarm | attack), ``false_alarm_rate`` =
      P(alarm | benign).
    """
    conf = np.asarray(conf, np.float64)
    C = conf.shape[0]
    tp = np.diag(conf)
    support = conf.sum(axis=1)
    predicted = conf.sum(axis=0)
    with np.errstate(divide="ignore", invalid="ignore"):
        precision = np.where(predicted > 0, tp / predicted, 0.0)
        recall = np.where(support > 0, tp / support, 0.0)
        f1 = np.where(precision + recall > 0,
                      2 * precision * recall / (precision + recall), 0.0)
    attack = np.arange(C) != benign_class
    attack_total = conf[attack].sum()
    benign_total = conf[benign_class].sum()
    alarms_on_attack = conf[attack][:, attack].sum()
    alarms_on_benign = conf[benign_class, attack].sum()
    return {
        "accuracy": float(tp.sum() / max(conf.sum(), 1.0)),
        "per_class_precision": precision,
        "per_class_recall": recall,
        "per_class_f1": f1,
        "macro_f1": float(f1[support > 0].mean()) if (support > 0).any()
        else 0.0,
        "detection_rate": float(alarms_on_attack / max(attack_total, 1.0)),
        "false_alarm_rate": float(alarms_on_benign / max(benign_total, 1.0)),
        "support": support,
    }


def sanitize_report(rep: dict) -> dict:
    """JSON-ready copy of a metrics report (numpy arrays -> lists) — the
    ONE serialization rule shared by every report printer (CLI stderr
    dumps, file-plane eval records)."""
    return {k: (v.tolist() if hasattr(v, "tolist") else v)
            for k, v in rep.items()}


def summarize_per_client(losses, accs, counts) -> dict:
    """Example-weighted aggregates + accuracy spread over per-client
    scores — ONE definition shared by the engine's vmapped per-client
    eval and the socket coordinator's wire-plane fan-out."""
    import numpy as np

    losses = np.asarray(losses, np.float64)
    accs = np.asarray(accs, np.float64)
    counts = np.asarray(counts, np.float64)
    w = counts / counts.sum()
    return {
        "weighted_loss": float((losses * w).sum()),
        "weighted_acc": float((accs * w).sum()),
        "acc_p10": float(np.percentile(accs, 10)),
        "acc_p50": float(np.percentile(accs, 50)),
        "acc_p90": float(np.percentile(accs, 90)),
    }
