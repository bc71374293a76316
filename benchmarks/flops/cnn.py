"""Operations the ``cnn`` family needs per example, from its shapes.

Counted: the multiply-adds of the convolutions and the dense layer, two
operations each.  Not counted: GroupNorm, ReLU, pooling, the loss and the
optimiser (a few per cent of the total, and not what a matrix unit's peak
measures).  The backward pass costs two more products per layer (input
gradient, weight gradient), except that the first convolution needs no
gradient for the image.
"""

from __future__ import annotations


def _conv_macs(model: dict, dataset: dict) -> list[int]:
    """Multiply-adds of each 3x3 same-padded convolution, in order."""
    h, w, c_in = dataset["input_shape"]
    macs = []
    for mult in (1, 2, 4):
        c_out = model["width"] * mult
        for _ in range(2):
            macs.append(h * w * 9 * c_in * c_out)
            c_in = c_out
        if h >= 2:
            h, w = h // 2, w // 2
    return macs


def forward_flops(model: dict, dataset: dict) -> float:
    """``model``/``dataset``: the configuration's sections of those names."""
    dense = model["width"] * 4 * model["num_classes"]
    return 2.0 * (sum(_conv_macs(model, dataset)) + dense)


def train_flops(model: dict, dataset: dict) -> float:
    first_conv = _conv_macs(model, dataset)[0]
    return 3.0 * forward_flops(model, dataset) - 2.0 * first_conv
