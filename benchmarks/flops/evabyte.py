"""Operations the ``evabyte`` family needs per example (one sequence of
``input_shape[0]`` bytes), and what its attention kernel needs, from the
shapes and the mask.

Counted, two operations a multiply-add: the four attention projections,
the three feed-forward products, the prediction head, and attention over
the keys the mask admits: query ``i`` sees the ``i % window + 1`` keys of
its own window up to itself and one summary for each chunk of the windows
before it (scores and weighted values, ``width`` multiply-adds each a
pair), plus the four pooling products that make the summaries some query
sees.  Not counted: the embedding look-up, RMSNorm, rotary, softmax, silu,
the loss and the optimiser; keys a kernel visits and masks; anything
recomputed.  The backward pass costs two more products per product.
"""

from __future__ import annotations


def _shape(model: dict, dataset: dict) -> tuple[int, int, int, int]:
    """(length, window, windows, chunks a window)."""
    length = dataset["input_shape"][0]
    window = min(model["window_size"], length)
    return length, window, length // window, window // model["chunk_size"]


def admitted_pairs(model: dict, dataset: dict) -> int:
    """Pairs of a query and a key (or a chunk's summary) it sees, summed
    over one sequence."""
    _, window, windows, per_window = _shape(model, dataset)
    own = windows * window * (window + 1) // 2
    earlier = per_window * window * windows * (windows - 1) // 2
    return own + earlier


def forward_flops(model: dict, dataset: dict) -> float:
    """``model``/``dataset``: the configuration's sections of those names."""
    length, window, _, _ = _shape(model, dataset)
    width = model["width"]
    per_layer = (length * (4 * width * width + 3 * width * model["ffn_dim"])
                 + 2 * width * admitted_pairs(model, dataset)
                 + 4 * width * (length - window))
    macs = (model["depth"] * per_layer + length * width
            * model["num_pred_heads"] * model["vocab_size"])
    return 2.0 * macs


def train_flops(model: dict, dataset: dict) -> float:
    return 3.0 * forward_flops(model, dataset)


def attention_flops(model: dict, dataset: dict, train: bool) -> float:
    """What the attention kernel of ``ops/attention.py`` has to do for one
    sequence through every layer: scores and weighted values over the
    admitted pairs; with ``train`` the backward's four products too (the
    scores it recomputes are not work done)."""
    forward = 2.0 * model["depth"] * 2 * model["width"] * admitted_pairs(
        model, dataset)
    return 3.0 * forward if train else forward


def attention_bytes(model: dict, dataset: dict, train: bool,
                    itemsize: int = 2) -> float:
    """The least the kernel moves between HBM and the chip for one
    sequence through every layer, each array once: forward it reads q, the
    keys and values of every window (its own and the summaries it is
    handed) and writes the output and a float32 log-sum a query and head;
    backward it reads those, the output's gradient and the log-sums, and
    writes three gradients."""
    length, window, windows, per_window = _shape(model, dataset)
    width = model["width"]
    queries = length * width * itemsize
    keys = windows * ((windows - 1) * per_window + window) * width * itemsize
    sums = length * model["num_heads"] * 4
    forward = 2 * queries + 2 * keys + sums
    backward = 4 * queries + 4 * keys + 2 * sums
    return float(model["depth"] * (forward + backward if train else forward))
