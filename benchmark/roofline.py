"""FLOPs and least bytes of a model's matrix work, from its layer inventory.

A configuration's ``flops/<config>.py`` lists the convolutions and matrix
products of one forward pass (``layers(settings)``), each with its
multiply-accumulates and element counts *per image*. One convention for every
model: 2 FLOPs per multiply-accumulate; a train step is forward + backward =
3 x forward (input gradient + weight gradient; the first layer computes no
input gradient but the convention keeps 3, as the published "6ND" does);
recomputation is never counted.
"""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # the least a bf16 step can move


def forward_macs_per_image(layers) -> float:
    return float(sum(layer["macs"] for layer in layers))


def train_flops_per_image(layers) -> float:
    return 3.0 * 2.0 * forward_macs_per_image(layers)


def mxu_min_seconds_per_step(layers, batch: int, peaks: dict) -> float:
    """Least time one chip could take for the matrix work of one train step.

    Per layer and per pass (forward, input gradient, weight gradient) the
    larger of FLOPs over peak FLOP/s and least bytes over peak bytes/s, where
    the least bytes of a pass are its two operands read once and its result
    written once in 2-byte elements. Summed over passes and layers: each pass
    has to leave its result in memory for the pass that reads it later.
    """
    total = 0.0
    for layer in layers:
        flops = 2.0 * layer["macs"] * batch
        act_in = layer["in"] * batch
        act_out = layer["out"] * batch
        other = layer["w"] if layer["w"] else 0  # weights do not grow with the batch
        nbytes = BYTES_PER_ELEMENT * (act_in + act_out + other)
        passes = 3 if layer.get("dgrad", True) else 2
        total += passes * max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return total
