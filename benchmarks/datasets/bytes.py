"""Dataset kind ``bytes``: packed documents of heavy-tailed length from a
fixed first-order Markov source over bytes, for a next-byte model with
several prediction heads (``benchmarks/traffic/generate.py`` says what a
kind is).

Each row is cut from a stream ``horizon`` longer than the sequence: ``x``
(n, L) and ``y`` (n, L, horizon) with ``y[r, i, j] = stream[r, i + 1 +
j]``, so every position is labelled for every head.  A byte ``b`` is id
``byte_offset + b`` (EvaByte's vocabulary: 64 special ids, then the 256
byte values); documents of ``min_document`` bytes and up (Pareto tail) are
packed back to back with one separator id between them and no boundary
mask.  The source (four likely successors a byte) is the same for every
seed, as the other kinds keep their class structure: seeds vary the
documents, the partition and the weights, not the task.  The next byte has
1.1 nats of entropy, so a loss can fall from ln 320.

A copy of ``colearn_federated_learning_tpu/data/synthetic.py``'s
generator, kept here so that a later PR cannot change what the benchmark
feeds the program; it draws both splits from the one generator it is
handed.
"""

from __future__ import annotations

import numpy as np

SOURCE_SEED = 20250101
SUCCESSOR_ODDS = (0.55, 0.25, 0.12, 0.08)


def byte_stream(n: int, length: int, horizon: int, rng: np.random.Generator,
                byte_offset: int, separator_id: int, min_document: int):
    successors = np.random.default_rng(SOURCE_SEED).integers(
        0, 256, size=(256, len(SUCCESSOR_ODDS)))
    total = length + horizon
    choice = rng.choice(len(SUCCESSOR_ODDS), size=(n, total),
                        p=SUCCESSOR_ODDS)
    fresh = rng.integers(0, 256, size=(n, total))
    # Document ends: cumulative lengths, each followed by one separator.
    lengths = (min_document * (1.0 + rng.pareto(
        1.1, size=(n, total // (min_document + 1) + 1)))).astype(np.int64)
    ends = np.cumsum(lengths + 1, axis=1) - 1
    is_sep = np.zeros((n, total), bool)
    rows = np.broadcast_to(np.arange(n)[:, None], ends.shape)
    inside = ends < total
    is_sep[rows[inside], ends[inside]] = True
    stream = np.empty((n, total), np.int32)
    state = fresh[:, 0]
    for t in range(total):
        stream[:, t] = np.where(is_sep[:, t], separator_id,
                                byte_offset + state)
        state = np.where(is_sep[:, t], fresh[:, t],
                         successors[state, choice[:, t]])
    ahead = np.arange(length)[:, None] + 1 + np.arange(horizon)[None, :]
    return stream[:, :length].copy(), stream[:, ahead]


def make(n: int, spec, rng: np.random.Generator, dataset_doc: dict):
    return byte_stream(n, spec.input_shape[0], dataset_doc["horizon"], rng,
                       dataset_doc["byte_offset"],
                       dataset_doc["separator_id"],
                       dataset_doc["min_document"])
