"""``dtpu_gdn_inverse`` (``distribuuuu_tpu/ops/gdn_inverse.inverse``): ``T = (I + a)⁻¹`` for ``N`` strictly
lower-triangular tiles ``a [N, Q, Q]`` float32, by the squarings of the nilpotent ``−a``:
``(I + n)(I + n²)(I + n⁴) …`` up to the power that vanishes, a tile's powers and partial products in VMEM.

Operations: the products the kernel performs, ``log2 Q − 1`` squarings and as many products with the
factors before them (ten at ``Q`` = 64; two of them at a time go through the matrix unit as one product of
``2Q`` rows, which is the same work), each a float32 ``Q x Q x Q`` product counted once at 2 FLOPs a
multiply-accumulate: the passes of bfloat16 pieces that make a float32 product on the matrix unit are the
implementation's, not the algorithm's, so the floor stays under every realisation. The zeros of the
triangle are not taken off (the kernel multiplies them). Bytes: ``a`` read and ``T`` written once.
"""

from benchmark import roofline


def cost(operands, results) -> dict:
    (_, (tiles, q, _)), = operands
    products = 2 * ((q - 1).bit_length() - 1)
    return {"flops": 2.0 * products * tiles * q ** 3, "bytes": roofline.array_bytes(operands + results), "matrix": True}
