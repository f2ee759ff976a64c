"""FLOPs and least bytes of a model's matrix work, from its layer inventory.

A configuration's ``flops/<config>.py`` lists the convolutions and matrix
products of one forward pass (``layers(settings)``), each with its
multiply-accumulates and element counts *per image*. One convention for every
model: 2 FLOPs per multiply-accumulate; a train step is forward + backward =
3 x forward (input gradient + weight gradient; the first layer computes no
input gradient but the convention keeps 3, as the published "6ND" does);
recomputation is never counted.

A tensor of a layer that a fused implementation need never write (the ``L x L`` scores and
weights of attention) is marked ``internal`` by the configuration's file: as many elements of
the layer's ``in`` + ``out`` are left out of its least bytes, so that the least time bounds
every implementation of the same work, XLA's dots or a kernel.

A Mosaic kernel of the compiled step is priced by ``kernels/<name>.py`` from the call's shapes
(`kernel_costs`): the operations it performs, its own recomputation included, and its operands
and results crossing HBM once.
"""

from __future__ import annotations

import math

from benchmark import files

BYTES_PER_ELEMENT = 2  # the least a bf16 step can move
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
            "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def forward_macs_per_image(layers) -> float:
    return float(sum(layer["macs"] for layer in layers))


def train_flops_per_image(layers) -> float:
    return 3.0 * 2.0 * forward_macs_per_image(layers)


def mxu_min_seconds_per_step(layers, batch: int, peaks: dict) -> float:
    """Least time one chip could take for the matrix work of one train step.

    Per layer and per pass (forward, input gradient, weight gradient) the
    larger of FLOPs over peak FLOP/s and least bytes over peak bytes/s, where
    the least bytes of a pass are its two operands read once and its result
    written once in 2-byte elements, less the elements marked ``internal``.
    Summed over passes and layers: each pass has to leave its result in memory
    for the pass that reads it later, unless it is internal.
    """
    total = 0.0
    for layer in layers:
        flops = 2.0 * layer["macs"] * batch
        act_in = layer["in"] * batch
        act_out = layer["out"] * batch
        other = layer["w"] if layer["w"] else 0  # weights do not grow with the batch
        nbytes = BYTES_PER_ELEMENT * (act_in + act_out + other - layer.get("internal", 0) * batch)
        passes = 3 if layer.get("dgrad", True) else 2
        total += passes * max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return total


def array_bytes(arrays) -> int:
    """Bytes of a list of ``(dtype, shape)`` as `hlo.kernel_calls` gives them; an unknown dtype is an error."""
    return sum(ITEMSIZE[dtype] * math.prod(shape) for dtype, shape in arrays)


def kernel_costs(calls: dict) -> dict[str, dict]:
    """instruction name -> ``{"kernel", "flops", "bytes", "matrix"}`` for the kernel calls of a compiled step.

    A kernel with no ``benchmark/kernels/<name>.py`` raises ``FileNotFoundError``: a traced run that
    meets one fails and prints no result, as a configuration with no reference does.
    """
    modules: dict[str, object] = {}
    out = {}
    for name, call in calls.items():
        kernel = call["kernel"]
        if kernel not in modules:
            modules[kernel] = files.load_module("kernels", kernel)
        out[name] = {"kernel": kernel, **modules[kernel].cost(call["operands"], call["results"])}
    return out


def kernel_min_seconds(cost: dict, peaks: dict) -> float:
    """Least time one chip could take for one call of a kernel: the larger of its FLOPs over peak
    FLOP/s and its bytes over peak bytes/s."""
    return max(cost["flops"] / peaks["bf16_flops_per_s"], cost["bytes"] / peaks["hbm_bytes_per_s"])
