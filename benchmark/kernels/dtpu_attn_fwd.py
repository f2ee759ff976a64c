"""``dtpu_attn_fwd`` (``ops/attention._self_attn_fwd_call``): softmax(q·kᵀ/√hd)·v for every head of a
packed ``qkv [B, L, 3·H·hd]``, one batch row a grid step; writes the output ``[B, L, H·hd]`` and the
rows' log-sum-exp ``[B, L, H]``.

Operations: the two matrix products of each head, scores and weighted values, ``L·L·hd``
multiply-accumulates each at 2 FLOPs. It recomputes nothing. The softmax's elementwise work is not
counted (the convention of ``roofline.py``: matrix work alone), nor the zeros of the lanes it masks
to pick one head of a 128-lane group: those are the implementation's, not the algorithm's. Bytes:
its operand and its two results crossing HBM once; the ``L x L`` scores and weights stay in VMEM.
"""

from benchmark import roofline

PRODUCTS = 2  # q·kᵀ, p·v


def cost(operands, results) -> dict:
    (_, (batch, tokens, width3)), = operands
    (_, (_, _, heads)) = results[1]
    width = width3 // 3
    macs = PRODUCTS * batch * heads * tokens * tokens * (width // heads)
    return {"flops": 2.0 * macs, "bytes": roofline.array_bytes(operands + results), "matrix": True}
