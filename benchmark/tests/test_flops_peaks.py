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


def test_an_internal_tensor_leaves_the_least_bytes():
    """The L x L scores of attention: with them the pass is bytes-bound, without them a fused
    implementation's bound is what is left, and the least time never falls under the FLOP time."""
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    layer = {"name": "scores", "macs": 50, "in": 20, "out": 400, "w": 0}
    written = roofline.mxu_min_seconds_per_step([layer], 1, pk)
    assert written == pytest.approx(3 * 2 * (20 + 400) / 10.0)  # 3 passes, 2 bytes an element
    fused = roofline.mxu_min_seconds_per_step([dict(layer, internal=400)], 1, pk)
    assert fused == pytest.approx(3 * 2 * 20 / 10.0)
    tiny = roofline.mxu_min_seconds_per_step([dict(layer, **{"in": 1, "internal": 400})], 1, pk)
    assert tiny == pytest.approx(3 * 2 * 50 / 100.0)  # the FLOP time is the floor
    # the committed configurations: ViT's two attention products mark their L x L tensors, resnet50 marks none
    vit = files.load_module("flops", "vit_b16").layers(SETTINGS)
    marked = [l for l in vit if l.get("internal")]
    assert len(marked) == 24 and all(l["internal"] == 12 * 197 * 197 for l in marked)
    assert not any(l.get("internal") for l in files.load_module("flops", "resnet50").layers(SETTINGS))
    real = peaks.lookup("TPU v5 lite")
    strip = [{k: v for k, v in l.items() if k != "internal"} for l in vit]
    assert roofline.mxu_min_seconds_per_step(vit, 128, real) < roofline.mxu_min_seconds_per_step(strip, 128, real)


FWD = {"kernel": "dtpu_attn_fwd", "operands": [("bf16", (128, 197, 2304))],
       "results": [("bf16", (128, 197, 768)), ("f32", (128, 197, 12))]}
BWD = {"kernel": "dtpu_attn_bwd", "results": [("bf16", (128, 197, 2304))],
       "operands": [("bf16", (128, 197, 2304)), ("bf16", (128, 197, 768)), ("bf16", (128, 197, 768)),
                    ("f32", (128, 197, 12))]}


def test_kernel_costs_from_the_calls_shapes():
    costs = roofline.kernel_costs({"dtpu_attn_fwd.12": FWD, "dtpu_attn_bwd.12": BWD})
    product = 2 * 128 * 12 * 197 * 197 * 64  # FLOPs of one L x L x hd product over the batch's heads
    fwd, bwd = costs["dtpu_attn_fwd.12"], costs["dtpu_attn_bwd.12"]
    assert fwd["flops"] == 2 * product and bwd["flops"] == 5 * product  # the backward's recomputed scores counted
    row = 128 * 197
    assert fwd["bytes"] == row * (2304 * 2 + 768 * 2 + 12 * 4)
    assert bwd["bytes"] == row * (2304 * 2 + 768 * 2 + 768 * 2 + 12 * 4 + 2304 * 2)
    assert fwd["matrix"] is True and bwd["matrix"] is True
    with pytest.raises(FileNotFoundError, match="zz_no_such_kernel"):
        roofline.kernel_costs({"k.1": dict(FWD, kernel="zz_no_such_kernel")})
    with pytest.raises(KeyError):
        roofline.array_bytes([("c64", (2, 2))])  # an unknown dtype is an error, never a guess


def test_kernel_and_matrix_rooflines_against_made_intervals():
    """Three steps of 1000 ns: a dot 0..400, the forward kernel 400..600, the backward 600..900, a vector op
    850..950 that overlaps it. Peaks chosen so that the kernels' least times are round numbers."""
    from benchmark import xplane

    costs = roofline.kernel_costs({"dtpu_attn_fwd.12": FWD, "dtpu_attn_bwd.12": BWD})
    fwd, bwd = costs["dtpu_attn_fwd.12"], costs["dtpu_attn_bwd.12"]
    pk = {"bf16_flops_per_s": fwd["flops"] / 50e-9, "hbm_bytes_per_s": fwd["bytes"] / 100e-9}
    least_fwd, least_bwd = roofline.kernel_min_seconds(fwd, pk), roofline.kernel_min_seconds(bwd, pk)
    assert least_fwd == pytest.approx(100e-9)  # bytes-bound: 100 ns against 50 ns of FLOPs
    assert least_bwd == pytest.approx(max(2.5 * 50e-9, bwd["bytes"] / fwd["bytes"] * 100e-9))
    ops, modules = [], []
    for base in (0, 1000, 2000, 3000, 4000):  # the reduction leaves the trace's edge steps out
        ops += [("dot.1", base, base + 400), ("dtpu_attn_fwd.12", base + 400, base + 600),
                ("dtpu_attn_bwd.12", base + 600, base + 900), ("add.2", base + 850, base + 950)]
        modules.append(("jit_step_training(1)", base, base + 990))
    trace = xplane.Trace({"/device:TPU:0": {xplane.OPS_LINE: ops, xplane.MODULES_LINE: modules}}, [])
    classes = {"dot.1": "mxu", "dtpu_attn_fwd.12": "kernel", "dtpu_attn_bwd.12": "kernel", "add.2": "vector"}
    layers = [{"name": "work", "macs": 1, "in": 1, "out": 1, "w": 0}]
    ctx = {"trace": trace, "peaks": pk, "classes": classes, "kernels": costs, "layers": layers,
           "batch_per_chip": 1, "roofline": roofline}
    kernel_pct = files.load_module("layer_metrics", "kernel_roofline_pct").read(ctx)
    assert kernel_pct == pytest.approx(100 * (least_fwd + least_bwd) / 500e-9)  # 200 + 300 ns a step
    # the matrix work's divisor is the dot and both kernels, 900 ns a step, whatever implements it
    least = roofline.mxu_min_seconds_per_step(layers, 1, pk)
    mxu_pct = files.load_module("layer_metrics", "mxu_roofline_pct").read(ctx)
    assert mxu_pct == pytest.approx(100 * least / 900e-9)
    # a kernel whose file says it does no matrix work stays out of that divisor
    ctx["kernels"] = {k: dict(v, matrix=False) for k, v in costs.items()}
    assert files.load_module("layer_metrics", "mxu_roofline_pct").read(ctx) == pytest.approx(100 * least / 400e-9)
    # kernels are no vector ops: 100 ns of 950 busy a step, the overlap counted once
    assert files.load_module("layer_metrics", "vector_share_pct").read(ctx) == pytest.approx(100 * 100 / 950)
    # and a step with no kernel gives the kernels' reader nothing to read
    ctx["kernels"] = {}
    assert files.load_module("layer_metrics", "kernel_roofline_pct").read(ctx) is None
