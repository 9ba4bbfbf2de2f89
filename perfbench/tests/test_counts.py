"""Operation and weight counts against hand counts."""
import json
import os

from perfbench import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _cfg(name):
    c = json.load(open(os.path.join(CONFIGS, name + ".json")))
    return c["cnn_spec"], c["input_shape"]


def test_weights_match_the_paper():
    assert flops.weight_count(*_cfg("mnist_cnn")) == 1_199_882
    assert flops.weight_count(*_cfg("deepdrive_cnn")) == 348_219


def test_mnist_flops_by_hand():
    # forward multiply-adds per sample: conv 26x26 out, conv 24x24 out,
    # dense 12*12*64 -> 128, dense 128 -> 10
    conv1 = 26 * 26 * 3 * 3 * 1 * 32
    conv2 = 24 * 24 * 3 * 3 * 32 * 64
    d1, d2 = 12 * 12 * 64 * 128, 128 * 10
    fwd = conv1 + conv2 + d1 + d2
    want = 2 * (fwd + fwd + (conv2 + d1 + d2))   # no input grad for layer 1
    assert want == 71_565_312                     # 71.6 MFLOP per sample
    assert flops.model_flops_per_sample(*_cfg("mnist_cnn")) == want


def test_deepdrive_flops_by_hand():
    # conv outputs 32x158, 14x77, 5x37, 3x35, 1x33 (flatten 2,112)
    convs = [32 * 158 * 25 * 3 * 24, 14 * 77 * 25 * 24 * 36,
             5 * 37 * 25 * 36 * 48, 3 * 35 * 9 * 48 * 64,
             1 * 33 * 9 * 64 * 64]
    dense = [2112 * 100, 100 * 50, 50 * 10, 10 * 1]
    fwd = sum(convs) + sum(dense)
    want = 2 * (fwd + fwd + fwd - convs[0])
    assert want == 250_081_572                    # 250 MFLOP per sample
    assert flops.model_flops_per_sample(*_cfg("deepdrive_cnn")) == want

