"""``dtpu_short_conv_fwd`` (``distribuuuu_tpu/ops/short_conv_kernels.forward``): the short causal depthwise
convolution over time and its `silu`, ``y = silu(b + Σ_j w[j] · x[t − (K−1) + j])`` for ``x [B, L, C]``,
``w [K, C]`` and, where there is one, a bias ``[1, C]``; a row's whole length of 128 channels in VMEM.

Operations: a multiply-add a tap and an element (and the bias's add), and the `silu` counted as four
(exponential, add, reciprocal, product), vector work all of it. Bytes: ``x``, ``w`` (and ``b``) read and ``y``
written once.
"""

from benchmark import roofline

SILU = 4


def cost(operands, results) -> dict:
    (_, (rows, length, channels)), (_, (taps, _)), *bias = operands
    per_element = 2 * taps + len(bias) + SILU
    return {"flops": float(per_element * rows * length * channels), "bytes": roofline.array_bytes(operands + results),
            "matrix": False}
