"""Matrix work of ResNet-50 v1.5, one forward pass, per image.

Walks the architecture (stem 7x7/2, stages [3, 4, 6, 3] of bottlenecks with
the stride on the 3x3, classifier) rather than holding a table, so the counts
follow ``TRAIN.IM_SIZE`` and ``MODEL.NUM_CLASSES``. The stem is the *logical*
7x7x3 convolution (147 MACs per output), not the 4x4x12 space-to-depth form
the program runs (192): work the algorithm needs, whatever implements it.
"""

from __future__ import annotations

STAGES = (3, 4, 6, 3)


def _conv(name, h_in, k, stride, cin, cout, dgrad=True):
    h_out = -(-h_in // stride)
    return {
        "name": name, "macs": h_out * h_out * k * k * cin * cout,
        "in": h_in * h_in * cin, "out": h_out * h_out * cout, "w": k * k * cin * cout,
        "dgrad": dgrad,
    }, h_out


def layers(settings: dict) -> list[dict]:
    size = int(settings["TRAIN"]["IM_SIZE"])
    classes = int(settings["MODEL"]["NUM_CLASSES"])
    out = []
    layer, h = _conv("stem.conv", size, 7, 2, 3, 64, dgrad=False)
    out.append(layer)
    h = -(-h // 2)  # 3x3/2 max pool
    cin = 64
    for stage, blocks in enumerate(STAGES):
        planes = 64 * 2**stage
        for i in range(blocks):
            stride = 2 if (stage > 0 and i == 0) else 1
            p = f"s{stage + 1}.b{i}"
            layer, _ = _conv(f"{p}.conv1", h, 1, 1, cin, planes)
            out.append(layer)
            layer, h_out = _conv(f"{p}.conv2", h, 3, stride, planes, planes)
            out.append(layer)
            layer, _ = _conv(f"{p}.conv3", h_out, 1, 1, planes, 4 * planes)
            out.append(layer)
            if stride != 1 or cin != 4 * planes:
                layer, _ = _conv(f"{p}.ds.conv", h, 1, stride, cin, 4 * planes)
                out.append(layer)
            h, cin = h_out, 4 * planes
    out.append({"name": "fc", "macs": cin * classes, "in": cin, "out": classes, "w": cin * classes})
    return out
