"""Dataset kind ``tokens_ahead``: the ``tokens`` kind's packed documents
(``benchmarks/datasets/tokens.py``: a fixed first-order Markov source over
a slice of a tokenizer's vocabulary, id 0 between documents) for a model
that predicts more than one token ahead.

``x`` (n, L) and ``y`` (n, L, horizon) with ``y[r, i, j] = stream[r, i + 1
+ j]``, so every position is labelled for every head.  The first ``L + 1``
tokens of a row are the ``tokens`` kind's for the same generator, so ``x``
is its ``x`` and ``y[..., 0]`` its ``y``; the ``horizon - 1`` tokens after
them continue the walk with draws made after the others (a fresh word
after a separator, else one of the word's four likely successors by their
odds; no document ends there: one label in ``L`` of the last head).

The configuration's ``dataset`` section gives ``horizon`` (1 + the model's
prediction modules), ``vocab_size`` and ``min_document``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.datasets import tokens


def make(n: int, spec, rng: np.random.Generator, dataset_doc: dict):
    length, horizon = spec.input_shape[0], dataset_doc["horizon"]
    vocab_size = dataset_doc["vocab_size"]
    x, y = tokens.token_stream(n, length, vocab_size, rng,
                               dataset_doc["min_document"])
    odds = tokens.word_odds(vocab_size)
    successors = 1 + np.random.default_rng(tokens.SOURCE_SEED).choice(
        vocab_size - 1, size=(vocab_size, len(tokens.SUCCESSOR_ODDS)), p=odds)
    stream = [x, y[:, -1:]]
    for _ in range(horizon - 1):
        last = stream[-1][:, -1]
        choice = rng.choice(len(tokens.SUCCESSOR_ODDS), size=n,
                            p=tokens.SUCCESSOR_ODDS)
        fresh = 1 + rng.choice(vocab_size - 1, size=n, p=odds)
        stream.append(np.where(last == 0, fresh, successors[last, choice])
                      .astype(np.int32)[:, None])
    stream = np.concatenate(stream, axis=1)
    ahead = np.arange(length)[:, None] + 1 + np.arange(horizon)[None, :]
    return x, stream[:, ahead]
