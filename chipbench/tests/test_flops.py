"""FLOP and byte counters against hand counts; the peak table."""
import json
import os

import pytest

from chipbench import flops, run
from chipbench.reference import paper_cnn

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    if name == "cifar10_cnn":
        # the paper's CIFAR-10 widths (Table II), a configuration that a
        # later cell can bring: the counters hold for it too
        return dict(cfg("mnist_cnn"), input_hw=[32, 32], input_channels=3,
                    fc1_out=300, params=224_978)
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_mnist_cnn_forward_flops_by_hand():
    # conv1 24x24x15 outputs of 5x5x1; conv2 8x8x28 of 5x5x15;
    # fc1 448->224; fc2 224->10; two FLOPs per multiply-add
    macs = 24 * 24 * 15 * 25 + 8 * 8 * 28 * 25 * 15 + 448 * 224 + 224 * 10
    assert flops.cnn_forward_flops(cfg("mnist_cnn")) == 2 * macs == 1_981_184


def test_cifar10_cnn_forward_flops_by_hand():
    # conv1 28x28x15 of 5x5x3; conv2 10x10x28 of 5x5x15; fc1 700->300
    macs = (28 * 28 * 15 * 75 + 10 * 10 * 28 * 375 + 700 * 300 + 300 * 10)
    assert flops.cnn_forward_flops(cfg("cifar10_cnn")) == 2 * macs \
        == 4_290_000


@pytest.mark.parametrize("name,params", [("mnist_cnn", 113_744),
                                         ("cifar10_cnn", 224_978)])
def test_parameter_counts_are_table_ii(name, params):
    c = cfg(name)
    assert paper_cnn.num_params(c) == c["params"] == params


def test_experiment_flops_by_hand():
    spec = {"local_iters": 20, "batch_size": 32, "rounds": 30,
            "test_samples": 1000}
    fwd = 1_981_184
    want = 340 * 20 * 32 * 3 * fwd + 31 * 1000 * fwd
    assert flops.experiment_flops(cfg("mnist_cnn"), spec, 340) == want


P_PAD = 114_176        # 113,744 columns in 512-column blocks


def cost(kernel):
    return run.load_metric(kernel + "_roofline").cost


def test_kernel_costs_from_call_shapes_by_hand():
    agg = [["f32", [1, P_PAD]], ["f32", [1, 40]], ["f32", [40, P_PAD]]]
    assert cost("flat_aggregate")(agg) == (
        2 * 40 * P_PAD, 4 * (P_PAD + 40 + 40 * P_PAD))
    div = [["f32", [40, 8]], ["f32", [40, P_PAD]], ["f32", [8, P_PAD]]]
    assert cost("pairwise_l2")(div) == (
        2 * 40 * 8 * P_PAD + 2 * 48 * P_PAD,
        4 * (40 * 8 + 40 * P_PAD + 8 * P_PAD))


def test_a_vmapped_call_counts_its_batch():
    one = [["f32", [1, P_PAD]], ["f32", [1, 10]], ["f32", [10, P_PAD]]]
    two = [["f32", [2, 1, P_PAD]], ["f32", [2, 1, 10]],
           ["f32", [2, 10, P_PAD]]]
    f1, b1 = cost("flat_aggregate")(one)
    assert cost("flat_aggregate")(two) == (2 * f1, 2 * b1)


def test_least_time_names_its_bound():
    pk = flops.peaks("TPU v5 lite")
    nbytes = 4 * (40 + 40 * P_PAD + P_PAD)
    t, bound = flops.least_seconds(2 * 40 * P_PAD, nbytes, pk)
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / 819e9)
    t, bound = flops.least_seconds(1e12, 1.0, pk)
    assert bound == "flops" and t == pytest.approx(1e12 / 197e12)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
