"""``dtpu_short_conv_bwd`` (``distribuuuu_tpu/ops/short_conv_kernels.backward``): the backward pass of the short
causal convolution and its `silu` for ``x [B, L, C]``, ``dy``, ``w [K, C]`` (and a bias ``[1, C]``): the
pre-activation again from ``x``, ``g = dy · silu'(pre)``, ``dx[t] = Σ_j w[j] · g[t + (K−1) − j]`` and a row's
sums ``Σ_t g[t] · x[t − (K−1) + j]`` (and ``Σ_t g``); ``g`` stays in VMEM.

Operations: the forward's pre-activation and `silu` again (a multiply-add a tap, four for the `silu`), five for
its derivative and the product with ``dy``, and a multiply-add a tap each for ``dx`` and the taps' sums (an add
for the bias's), vector work all of it. Bytes: ``x``, ``dy``, ``w`` (and ``b``) read, ``dx`` and the sums
written once.
"""

from benchmark import roofline

SILU, SILU_GRAD = 4, 5


def cost(operands, results) -> dict:
    (_, (rows, length, channels)), _, (_, (taps, _)), *bias = operands
    per_element = 3 * 2 * taps + 2 * len(bias) + SILU + SILU_GRAD
    return {"flops": float(per_element * rows * length * channels), "bytes": roofline.array_bytes(operands + results),
            "matrix": False}
