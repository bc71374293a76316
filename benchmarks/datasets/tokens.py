"""Dataset kind ``tokens``: packed documents of heavy-tailed length from a
fixed first-order Markov source over a slice of a tokenizer's vocabulary,
for a next-token model (``benchmarks/traffic/generate.py`` says what a
kind is).

Each row is cut from a stream one longer than the sequence: ``x`` (n, L)
and ``y`` (n, L) with ``y[r, i] = stream[r, i + 1]``, so every position is
labelled.  Ids are drawn from the slice ``[0, vocab_size)``: id 0 stands
between documents, the words ``1 .. vocab_size - 1`` follow a
Zipf-Mandelbrot law (odds ``1 / (id + 2.7)``), as a tokenizer's ids do.
Documents of ``min_document`` tokens and up (Pareto tail) are packed back
to back with no boundary mask.  The source (four likely successors a
word, themselves drawn by that law; a document's first word likewise) is
the same for every seed, as the other kinds keep their class structure:
seeds vary the documents, the partition and the weights, not the task.
The unigram statistics are heavy-tailed, so a loss falls from ``ln
vocab_size`` within a few steps; the next token has 1.1 nats of entropy.

A copy of ``colearn_federated_learning_tpu/data/synthetic.py``'s
generator, kept here so that a later PR cannot change what the benchmark
feeds the program; it draws both splits from the one generator it is
handed.
"""

from __future__ import annotations

import numpy as np

SOURCE_SEED = 20250101
SUCCESSOR_ODDS = (0.55, 0.25, 0.12, 0.08)
ZIPF_SHIFT = 2.7


def word_odds(vocab_size: int) -> np.ndarray:
    odds = 1.0 / (np.arange(1, vocab_size) + ZIPF_SHIFT)
    return odds / odds.sum()


def token_stream(n: int, length: int, vocab_size: int,
                 rng: np.random.Generator, min_document: int):
    odds = word_odds(vocab_size)
    successors = 1 + np.random.default_rng(SOURCE_SEED).choice(
        vocab_size - 1, size=(vocab_size, len(SUCCESSOR_ODDS)), p=odds)
    total = length + 1
    choice = rng.choice(len(SUCCESSOR_ODDS), size=(n, total),
                        p=SUCCESSOR_ODDS)
    fresh = 1 + rng.choice(vocab_size - 1, size=(n, total), p=odds)
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
        stream[:, t] = np.where(is_sep[:, t], 0, state)
        state = np.where(is_sep[:, t], fresh[:, t],
                         successors[state, choice[:, t]])
    return stream[:, :length].copy(), stream[:, 1:].copy()


def make(n: int, spec, rng: np.random.Generator, dataset_doc: dict):
    return token_stream(n, spec.input_shape[0], dataset_doc["vocab_size"],
                        rng, dataset_doc["min_document"])
