import json
from pathlib import Path

import flops

BENCH = Path(__file__).resolve().parents[1]


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_resnet50_forward_macs_match_the_paper():
    macs = flops.resnet_forward_macs_per_image(_config("resnet50-imagenet"))
    # He et al. 2015, Table 1: 3.8e9 "FLOPs" (multiply-adds) for 50 layers;
    # the stride-in-3x3 variant is 4.1e9
    assert 3.8e9 <= macs <= 4.1e9
    assert flops.resnet_train_flops_per_image(
        _config("resnet50-imagenet")) == 6 * macs


def test_resnet50_has_53_convolutions_and_a_dense_layer():
    shapes = list(flops.resnet_conv_shapes(_config("resnet50-imagenet")))
    assert len(shapes) == 1 + 16 * 3 + 4 + 1
    assert shapes[0] == ("stem", 112, 112, 7, 7, 3, 64)
    assert shapes[-1] == ("out", 1, 1, 1, 1, 2048, 1000)
    assert shapes[-2][1:3] == (7, 7)


def test_gpt2_medium_flops_per_token():
    cfg = _config("gpt2-medium")
    per_token = flops.lm_train_flops_per_token(cfg, 1024)
    blocks = 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096)
    assert blocks == 301_989_888
    assert per_token == 3 * (2 * blocks + 2 * 1024 * 50257 + 24 * 2 * 1024 * 1024)
    assert abs(per_token - 2.2717e9) < 1e6
    # 100 % of the v5e's 197 TFLOP/s would be 86.7 k tokens/s
    assert 86e3 < 197e12 / per_token < 87.5e3


def test_flash_counts():
    ops, nbytes = flops.flash_flops_bytes(16, 16, 1024, 64, True)
    assert ops == 6 * 0.5 * 2 * 16 * 16 * 1024 * 1024 * 64
    assert nbytes == 12 * 16 * 16 * 1024 * 64 * 2
    full, _ = flops.flash_flops_bytes(16, 16, 1024, 64, False)
    assert full == 2 * ops
