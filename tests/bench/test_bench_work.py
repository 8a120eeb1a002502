"""Required-work counts of the on-chip benchmark
(benchmarks/chip/chipbench/work.py) against hand counts."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmarks", "chip"))

from chipbench import work  # noqa: E402

T = work.TILE
SHAPE = {"n_layers": 2, "d_model": 256, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 128, "d_ff": 384, "vocab_rows": 1024}


def test_one_product_forward_dx_and_dw():
    calls = work.product(rows=8, k=256, n=128, live=T * T, itemsize=2)
    assert calls["fwd"] == (2 * 8 * T * T, 2 * (8 * 256 + T * T + 8 * 128))
    assert calls["dx"] == (2 * 8 * T * T, 2 * (8 * 128 + T * T + 8 * 256))
    assert calls["dw"] == (2 * 8 * T * T, 2 * (8 * 256 + 8 * 128 + T * T))


def test_stacked_leaf_counts_each_layers_own_live_tiles():
    # wq keeps 1 tile in layer 0 and 3 in layer 1: the union over the
    # layers (3 or 4 tiles) must not be what is counted
    live = {"attn/wq": [1, 3]}
    got = work.lm_train_step(SHAPE, live, batch=2, seq=4)
    rows = 8
    assert [c[0] for c in got["bsmm"]] == [2 * rows * T * T] * 3 + \
        [2 * rows * 3 * T * T] * 3
    routed = 3 * 2 * rows * 4 * T * T
    pairs = 2 * 2 * 4 * 5 / 2
    attention = 2 * 3 * 4 * pairs * 128
    head = 3 * 2 * rows * 256 * 1024
    assert got["attention_flops"] == attention
    assert got["head_flops"] == head
    assert got["flops"] == routed + attention + head


def test_projection_dims_follow_the_heads():
    dims = work.projection_dims(SHAPE)
    assert dims["attn/wq"] == (256, 256)
    assert dims["attn/wk"] == (256, 128)
    assert dims["attn/wo"] == (256, 256)
    assert dims["mlp/down"] == (384, 256)


def test_conv_step_counts_the_feature_maps_not_the_unroll():
    # a 3x3 convolution 32x32x3 -> 32x32x64 on the data, and the head
    layers = [{"hw": 1024, "x": 1024 * 3, "y": 1024 * 64, "live": 27 * 64,
               "data": True},
              {"hw": 1, "x": 512, "y": 10, "live": 1280}]
    got = work.conv_train_step(layers, batch=2, itemsize=4)
    # the convolution on the data needs no input gradient
    assert len(got["conv"]) == 2 + 3
    conv = (2 * 2048 * 27 * 64, 4 * (2 * 3072 + 27 * 64 + 2 * 65536))
    head = (2 * 2 * 1280, 4 * (2 * 512 + 1280 + 2 * 10))
    assert got["conv"] == [conv, conv, head, head, head]
    assert got["flops"] == 2 * conv[0] + 3 * head[0]


def test_a_wide_convolution_reads_its_input_once():
    # 2x2x512 -> 2x2x512 at 10% of the (4608, 512) unroll: its input is
    # 2048 elements an image, not the 4 x 4608 of the unroll
    live = 0.1 * 4608 * 512
    got = work.conv_train_step(
        [{"hw": 4, "x": 2048, "y": 2048, "live": live}], batch=128,
        itemsize=4)
    fwd, dx, dw = got["conv"]
    assert fwd == dx == dw == (2 * 128 * 4 * live,
                               4 * (128 * 2048 + live + 128 * 2048))


def test_cnn_layers_give_each_feature_map():
    from chipbench import cells
    from chipbench.jobs import cnn_retrain
    cell = cells.load_cell("vgg16-cifar10.retrain.t10")
    cell.config["cnn"].update(
        convs=[{"out_channels": 32}, {"out_channels": 64, "pool": True}],
        image_size=8)
    cfg = cnn_retrain.cnn_config(cell.config)
    masks = {"convs": [{"w": np.ones((3, 3, 3, 32))},
                       {"w": np.ones((3, 3, 32, 64))}],
             "head": {"w": np.ones((64, 10))}}
    got = cnn_retrain.conv_layers(cfg, masks)
    assert got == [
        {"hw": 64, "x": 64 * 3, "y": 64 * 32, "live": 27 * 32.0,
         "weights": 27 * 32, "data": True},
        {"hw": 64, "x": 64 * 32, "y": 64 * 64, "live": 288 * 64.0,
         "weights": 288 * 64, "data": False},
        {"hw": 1, "x": 64, "y": 10, "live": 640.0, "weights": 640}]


@pytest.mark.parametrize("calls,least,bound", [
    ([(197e12, 1.0)], 1.0, "flops"),
    ([(1.0, 819e9), (1.0, 819e9)], 2.0, "bytes"),
    ([(394e12, 1.0), (1.0, 819e9)], 3.0, "flops"),
])
def test_least_time_adds_each_products_larger_term(calls, least, bound):
    got, which = work.least_seconds(calls, 197e12, 819e9)
    assert got == pytest.approx(least)
    assert which == bound
