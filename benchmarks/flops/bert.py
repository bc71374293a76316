"""Operations the ``bert`` family needs per example (one sequence), from
its shapes.

Counted: the multiply-adds of the four attention projections, the two
attention products (scores and weighted values, over all ``seq_len``
positions: shapes are static and padding is computed), the two
feed-forward layers and the classifier, two operations each.  Not
counted: embedding look-ups, LayerNorm, softmax, GELU, the loss and the
optimiser.  The backward pass costs two more products per matrix product.
"""

from __future__ import annotations


def forward_flops(model: dict, dataset: dict) -> float:
    """``model``/``dataset``: the configuration's sections of those names."""
    length = dataset["input_shape"][0]
    width = model["width"]
    per_token = (4 * width * width            # query, key, value, out
                 + 2 * length * width         # scores, weighted values
                 + 2 * width * 4 * width)     # feed-forward, 4x width
    macs = model["depth"] * length * per_token + width * model["num_classes"]
    return 2.0 * macs


def train_flops(model: dict, dataset: dict) -> float:
    return 3.0 * forward_flops(model, dataset)
