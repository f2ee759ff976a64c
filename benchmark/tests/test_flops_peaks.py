"""The FLOP functions and the peaks table: one convention for both models."""

import pytest

from benchmark import files, peaks, roofline

SETTINGS = {"TRAIN": {"IM_SIZE": 224}, "MODEL": {"NUM_CLASSES": 1000}}


@pytest.mark.parametrize("config,gmac", [("resnet50", 4.09), ("vit_b16", 17.56)])
def test_forward_macs_match_the_published_totals(config, gmac):
    layers = files.load_module("flops", config).layers(SETTINGS)
    macs = roofline.forward_macs_per_image(layers)
    assert abs(macs / 1e9 - gmac) / gmac < 0.01
    # a train step is 2 FLOPs per MAC, forward + backward = 3 x forward
    assert roofline.train_flops_per_image(layers) == pytest.approx(macs * 2 * 3)


def test_resnet50_counts_the_logical_stem():
    layers = files.load_module("flops", "resnet50").layers(SETTINGS)
    stem = layers[0]
    assert stem["macs"] == 112 * 112 * 7 * 7 * 3 * 64  # 147 MACs an output, not the s2d form's 192
    assert len(layers) == 1 + 16 * 3 + 4 + 1


def test_least_time_is_at_least_the_flop_time():
    pk = peaks.lookup("TPU v5 lite")
    for config, batch in (("resnet50", 256), ("vit_b16", 128)):
        layers = files.load_module("flops", config).layers(SETTINGS)
        least = roofline.mxu_min_seconds_per_step(layers, batch, pk)
        flop_time = roofline.train_flops_per_image(layers) * batch / pk["bf16_flops_per_s"]
        # the first layer computes no input gradient, so the FLOP time of 3 passes may exceed by that
        first = 2.0 * layers[0]["macs"] * batch / pk["bf16_flops_per_s"]
        assert least >= flop_time - first - 1e-12


def test_peaks_table():
    pk = peaks.lookup("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.lookup("cpu")
