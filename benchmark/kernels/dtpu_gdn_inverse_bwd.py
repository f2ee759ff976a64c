"""``dtpu_gdn_inverse_bwd`` (``distribuuuu_tpu/ops/gdn_inverse.inverse_bwd``): the inverse's own backward
pass, ``da = −Tᵀ dT Tᵀ`` for ``T, dT [N, Q, Q]`` float32, the transpose and the product between the two in
VMEM.

Operations: its two float32 ``Q x Q x Q`` products a tile, each counted once at 2 FLOPs a
multiply-accumulate (see ``dtpu_gdn_inverse.py``). Bytes: ``T`` and ``dT`` read and ``da`` written once.
"""

from benchmark import roofline

PRODUCTS = 2  # dT·Tᵀ, Tᵀ·(that)


def cost(operands, results) -> dict:
    (_, (tiles, q, _)), _ = operands
    return {"flops": 2.0 * PRODUCTS * tiles * q ** 3, "bytes": roofline.array_bytes(operands + results), "matrix": True}
