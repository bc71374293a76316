"""Deterministic synthetic datasets with the shapes of the benchmark corpora.

This sandbox has no network egress and no dataset files on disk, so the
registry falls back to class-conditional synthetic data whose shapes/dtypes
match MNIST / CIFAR-10 / CIFAR-100 / AG-News / FEMNIST.  The generator is a
fixed random class-prototype plus noise, which makes the tasks genuinely
learnable — accuracy curves rise across federated rounds, exercising the
same code paths a real corpus would (the reference validated by watching
accuracy curves, SURVEY.md §4).

Generation is numpy on host: it runs once at startup and produces the
static-shape arrays the jit path consumes.
"""

from __future__ import annotations

import numpy as np


def synthetic_image_classification(
    n: int,
    image_shape: tuple[int, int, int],
    n_classes: int,
    seed: int = 0,
    noise: float = 0.35,
    proto_seed: int = 1234,
) -> tuple[np.ndarray, np.ndarray]:
    """Images = smoothed class prototype + Gaussian noise, in [0, 1].

    Prototypes are low-frequency random fields so conv nets (and patching
    ViTs) have spatial structure to exploit, not just a per-pixel bias.
    ``proto_seed`` is SEPARATE from ``seed`` so train and test splits share
    one class structure (generalization is real) while drawing disjoint
    samples.
    """
    rng = np.random.default_rng(seed)
    h, w, c = image_shape
    # Low-res prototype upsampled → low-frequency spatial structure.
    lo = max(2, h // 4), max(2, w // 4)
    proto_rng = np.random.default_rng(proto_seed)
    protos_lo = proto_rng.normal(0.5, 0.5, size=(n_classes, *lo, c))
    protos = np.stack(
        [
            np.kron(p, np.ones((h // lo[0] + 1, w // lo[1] + 1))[..., None])[:h, :w, :]
            for p in protos_lo
        ]
    )
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    x = protos[y] + rng.normal(0.0, noise, size=(n, h, w, c))
    x = np.clip(x, 0.0, 1.0).astype(np.float32)
    return x, y


def synthetic_text_classification(
    n: int,
    seq_len: int,
    vocab_size: int,
    n_classes: int,
    seed: int = 0,
    signal_tokens: int = 48,
) -> tuple[np.ndarray, np.ndarray]:
    """Token sequences where each class over-samples its own token bucket.

    Shapes match a wordpiece-tokenized AG-News batch: int32 ids of
    (n, seq_len) with id 0 reserved for padding.
    """
    rng = np.random.default_rng(seed)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    # Class-specific "topic vocabulary" buckets, disjoint, above id 1000.
    base = 1000
    buckets = [
        np.arange(base + k * signal_tokens, base + (k + 1) * signal_tokens)
        for k in range(n_classes)
    ]
    x = rng.integers(1, vocab_size, size=(n, seq_len)).astype(np.int32)
    topic_mask = rng.random((n, seq_len)) < 0.3
    for k in range(n_classes):
        rows = y == k
        topical = rng.choice(buckets[k], size=(int(rows.sum()), seq_len))
        x[rows] = np.where(topic_mask[rows], topical, x[rows])
    # Variable lengths with 0-padding, like real tokenized text.
    lengths = rng.integers(seq_len // 4, seq_len + 1, size=n)
    pad = np.arange(seq_len)[None, :] >= lengths[:, None]
    x[pad] = 0
    return x, y


def synthetic_traffic_classification(
    n: int,
    shape: tuple[int, int],
    n_classes: int,
    seed: int = 0,
    noise: float = 0.4,
    proto_seed: int = 1234,
) -> tuple[np.ndarray, np.ndarray]:
    """IoT network-traffic-like sequences: (T, F) feature windows.

    CoLearn's actual task is network-anomaly detection on IoT traffic
    (SURVEY.md §0); with no corpora on disk this generator produces
    class-conditional TEMPORAL structure a temporal conv net can exploit:
    each class is a smooth per-feature random walk (think rolling
    byte/packet-rate statistics) plus class-specific periodic bursts
    (think beaconing/scan periodicity) — signals that distinguish attack
    families in real flow data.
    """
    t_len, n_feat = shape
    rng = np.random.default_rng(seed)
    proto_rng = np.random.default_rng(proto_seed)
    # Smooth per-class baselines: cumulative sums, normalized.
    base = np.cumsum(
        proto_rng.normal(0.0, 1.0, size=(n_classes, t_len, n_feat)), axis=1
    )
    base /= np.abs(base).max(axis=(1, 2), keepdims=True) + 1e-6
    # Class-periodic bursts on a per-class subset of features.
    t = np.arange(t_len)[None, :, None]
    periods = proto_rng.integers(3, max(4, t_len // 4),
                                 size=(n_classes, 1, 1))
    phase = proto_rng.uniform(0, 2 * np.pi, size=(n_classes, 1, n_feat))
    gates = (proto_rng.uniform(size=(n_classes, 1, n_feat)) < 0.5)
    bursts = np.sin(2 * np.pi * t / periods + phase) * gates
    protos = (base + 0.7 * bursts).astype(np.float32)

    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    x = protos[y] + rng.normal(0.0, noise, size=(n, t_len, n_feat))
    return x.astype(np.float32), y


# The byte source behind ``synthetic_byte_stream``: EvaByte's vocabulary
# is 64 special ids followed by the 256 byte values.
BYTE_OFFSET = 64
SEPARATOR_ID = 3
_SOURCE_SEED = 20250101
_SUCCESSOR_ODDS = (0.55, 0.25, 0.12, 0.08)


def _document_ends(rng, n: int, total: int) -> np.ndarray:
    """(n, total) bool: where a document of 32 symbols and up (Pareto
    tail) ends and one separator stands."""
    lengths = (32 * (1.0 + rng.pareto(1.1, size=(n, total // 33 + 1)))
               ).astype(np.int64)
    ends = np.cumsum(lengths + 1, axis=1) - 1
    is_sep = np.zeros((n, total), bool)
    rows = np.broadcast_to(np.arange(n)[:, None], ends.shape)
    inside = ends < total
    is_sep[rows[inside], ends[inside]] = True
    return is_sep


def _markov_walk(is_sep, fresh, successors, choice, separator_id: int,
                 offset: int) -> np.ndarray:
    """(n, total) int32 ids: a first-order walk over ``successors`` (which
    of a symbol's likely successors: ``choice``), a separator where
    ``is_sep`` says and a fresh symbol (``fresh``) after it; symbol ``s``
    is id ``offset + s``."""
    n, total = is_sep.shape
    stream = np.empty((n, total), np.int32)
    state = fresh[:, 0]
    for t in range(total):
        stream[:, t] = np.where(is_sep[:, t], separator_id, offset + state)
        state = np.where(is_sep[:, t], fresh[:, t],
                         successors[state, choice[:, t]])
    return stream


def synthetic_byte_stream(
    n: int,
    seq_len: int,
    horizon: int = 8,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Packed documents of heavy-tailed length from a fixed first-order
    Markov source over bytes, for a next-byte model with ``horizon``
    prediction heads (models/evabyte.py).

    Each row is cut from a stream ``horizon`` longer than ``seq_len``:
    ``x`` (n, seq_len) int32 and ``y`` (n, seq_len, horizon) with
    ``y[r, i, j] = stream[r, i + 1 + j]``, so every position is labelled.
    A byte ``b`` is id ``64 + b``; documents (32 bytes and up, Pareto
    tail) are packed back to back with one separator id between them and
    no boundary mask.  The source (four likely successors a byte, the same
    for every seed) is what makes the task learnable: the next byte has
    1.1 nats of entropy, not ln 256.
    """
    successors = np.random.default_rng(_SOURCE_SEED).integers(
        0, 256, size=(256, 4))
    rng = np.random.default_rng(seed)
    total = seq_len + horizon
    choice = rng.choice(4, size=(n, total), p=np.array(_SUCCESSOR_ODDS))
    fresh = rng.integers(0, 256, size=(n, total))
    stream = _markov_walk(_document_ends(rng, n, total), fresh, successors,
                          choice, SEPARATOR_ID, BYTE_OFFSET)
    ahead = np.arange(seq_len)[:, None] + 1 + np.arange(horizon)[None, :]
    return stream[:, :seq_len].copy(), stream[:, ahead]


# The token source behind ``synthetic_token_stream``: word ids follow a
# Zipf-Mandelbrot law, as a tokenizer's do; id 0 separates documents.
_ZIPF_SHIFT = 2.7


def zipf_odds(vocab_size: int) -> np.ndarray:
    """Odds of the word ids 1..``vocab_size`` - 1 (id 0 is the
    separator)."""
    odds = 1.0 / (np.arange(1, vocab_size) + _ZIPF_SHIFT)
    return odds / odds.sum()


def synthetic_token_stream(
    n: int,
    seq_len: int,
    vocab_size: int,
    seed: int = 0,
    horizon: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Packed documents of heavy-tailed length from a fixed first-order
    Markov source over ``vocab_size`` token ids, for a next-token model
    (models/nemotron_h.py).

    Each row is cut from a stream one longer than ``seq_len``: ``x`` (n,
    seq_len) int32 and ``y`` (n, seq_len) with ``y[r, i] = stream[r, i +
    1]``.  With ``horizon`` (a model that also predicts further ahead,
    models/xing4.py) the stream is ``horizon`` longer and ``y`` is (n,
    seq_len, horizon), ``y[r, i, j] = stream[r, i + 1 + j]``.  Documents
    (32 tokens and up, Pareto tail) are packed back to
    back with id 0 between them and no boundary mask.  A word has four
    likely successors, themselves drawn by Zipf's law and the same for
    every seed, and so has a document's first word: the stream's unigram
    statistics are heavy-tailed (a loss falls from ln V within a few
    steps), its next token has 1.1 nats of entropy.
    """
    odds = zipf_odds(vocab_size)
    # A word is its own id: row 0 of the table is no word's.
    successors = 1 + np.random.default_rng(_SOURCE_SEED).choice(
        vocab_size - 1, size=(vocab_size, 4), p=odds)
    rng = np.random.default_rng(seed)
    total = seq_len + max(horizon, 1)
    choice = rng.choice(4, size=(n, total), p=np.array(_SUCCESSOR_ODDS))
    fresh = 1 + rng.choice(vocab_size - 1, size=(n, total), p=odds)
    stream = _markov_walk(_document_ends(rng, n, total), fresh, successors,
                          choice, 0, 0)
    if not horizon:
        return stream[:, :seq_len].copy(), stream[:, 1:].copy()
    ahead = np.arange(seq_len)[:, None] + 1 + np.arange(horizon)[None, :]
    return stream[:, :seq_len].copy(), stream[:, ahead]
