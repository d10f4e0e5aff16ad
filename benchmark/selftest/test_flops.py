"""``benchmark/flops.py`` against hand counts for one layer of each kind.

    python3 -m pytest benchmark/selftest/test_flops.py -q
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import flops  # noqa: E402

LM = dict(hidden=2048, heads=16, kv_heads=8, intermediate=8192, vocab=92544)


def test_lm_layer_by_hand():
    # q 2048x2048, packed k/v 2048x(2*8*128), proj 2048x2048, three 2048x8192
    by_hand = 2048 * 2048 + 2048 * 2048 + 2048 * 2048 + 3 * 2048 * 8192
    assert by_hand == 62_914_560
    assert flops.dense_lm_layer_matmul_params(2048, 16, 8, 8192) == by_hand


def test_lm_step_by_hand():
    got = flops.dense_lm_train(**LM, layers=2, batch=1, seq=16384)
    tokens = 16384
    blocks = 6 * 2 * 62_914_560 * tokens
    head = 6 * 2048 * 92544 * tokens            # the head, not the embedding
    attention = 12 * 2 * 16384 * 2048 // 2 * tokens
    assert got["blocks"] == blocks and got["head"] == head
    assert got["attention"] == attention
    assert got["flops"] == blocks + head + attention
    assert got["matmul_params"] == 2 * 62_914_560 + 2048 * 92544
    # at 2 layers the head is half the work and attention 17.5% at 16384
    # (at the source's 24 layers: 8% and 32%)
    assert 0.17 < attention / got["flops"] < 0.18
    assert 0.49 < head / got["flops"] < 0.50


def test_resnet50_layer_by_hand_and_total():
    layers = flops.resnet_bottleneck_layers(
        stage_sizes=(3, 4, 6, 3), num_filters=64, image=224,
        num_classes=1000)
    assert len(layers) == 53 + 1                      # 53 convolutions, dense
    by_name = {l["name"]: l for l in layers}
    conv2 = by_name["stage0.block0.conv2"]            # 3x3, 64 -> 64 at 56x56
    assert flops.layer_forward_flops(conv2) == 2 * 56 * 56 * 9 * 64 * 64
    first = by_name["conv_init"]                      # 7x7, 3 -> 64 at 112
    assert flops.layer_forward_flops(first) == 2 * 112 * 112 * 49 * 3 * 64
    down = by_name["stage1.block0.conv2"]             # stride on the 3x3
    assert (down["out"], down["cin"], down["cout"]) == (28, 128, 128)
    assert by_name["stage3.block0.proj"]["cin"] == 1024
    got = flops.resnet_train(stage_sizes=(3, 4, 6, 3), num_filters=64,
                             image=224, num_classes=1000, batch=256)
    # the published figure for ResNet-50 v1.5 at 224 is about 4.1 GMAC
    assert 4.0e9 < got["forward_per_image"] / 2 < 4.2e9
    assert got["train_per_image"] == 3 * got["forward_per_image"] \
        - flops.layer_forward_flops(first)
    assert got["flops"] == 256 * got["train_per_image"]


def test_flash_kernels_by_hand():
    shape = dict(batch=1, seq=16384, heads=16, head_dim=128)
    pairs = 16384 * 16385 // 2
    fwd = flops.flash_kernel("fwd", **shape)
    assert fwd["flops"] == 2 * 2 * 128 * pairs * 16       # scores, values
    assert fwd["bytes"] == 16 * (4 * 16384 * 128 * 2 + 16384 * 4)
    assert flops.flash_kernel("dq", **shape)["flops"] == 3 * 2 * 128 * pairs * 16
    assert flops.flash_kernel("dkv", **shape)["flops"] == 4 * 2 * 128 * pairs * 16
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = flops.roofline_seconds(fwd, peaks)
    assert bound == "compute" and abs(seconds - fwd["flops"] / 197e12) < 1e-12
    short = flops.flash_kernel("fwd", batch=1, seq=128, heads=1, head_dim=128)
    assert flops.roofline_seconds(short, peaks)[1] == "memory"
