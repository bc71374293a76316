"""The operation counts against counts made by hand."""

import pytest

from benchmarks.flops import bert, cnn


def test_cnn_width_64():
    model = {"width": 64, "num_classes": 10}
    dataset = {"input_shape": [32, 32, 3]}
    # Multiply-adds, by hand: six 3x3 convolutions at 32x32, 16x16 and 8x8
    # positions, then 256 -> 10.
    convs = [1024 * 27 * 64, 1024 * 576 * 64,
             256 * 576 * 128, 256 * 1152 * 128,
             64 * 1152 * 256, 64 * 2304 * 256]
    macs = sum(convs) + 2560
    assert macs == 152_766_976
    assert cnn.forward_flops(model, dataset) == 2 * macs
    assert cnn.forward_flops(model, dataset) == pytest.approx(0.31e9, rel=0.02)
    # Backward: two more products per layer, the image's gradient left out.
    assert cnn.train_flops(model, dataset) == 6 * macs - 2 * convs[0]
    # One round of cohort 64 x 8 steps x batch 32.
    assert cnn.train_flops(model, dataset) * 64 * 8 * 32 == pytest.approx(
        15e12, rel=0.01)


def test_bert_base_seq_128():
    model = {"width": 768, "depth": 12, "num_classes": 4}
    dataset = {"input_shape": [128]}
    per_token_layer = (4 * 768 * 768        # q, k, v, out
                       + 2 * 128 * 768      # scores, weighted values
                       + 2 * 768 * 3072)    # feed-forward
    assert per_token_layer == 7_274_496
    macs = 12 * 128 * per_token_layer + 768 * 4
    assert bert.forward_flops(model, dataset) == 2 * macs
    assert bert.train_flops(model, dataset) == 6 * macs
    # About 6 x parameters x tokens for the 85M encoder parameters.
    assert bert.train_flops(model, dataset) == pytest.approx(
        6 * 85e6 * 128, rel=0.03)
