"""``dtpu_attn_bwd`` (``ops/attention._self_attn_bwd_call``): the gradient of `dtpu_attn_fwd` with
respect to the packed ``qkv``, from ``qkv``, the output, the output's gradient and the rows'
log-sum-exp; one batch row a grid step, one packed ``d_qkv [B, L, 3·H·hd]`` written.

Operations: five matrix products a head, ``L·L·hd`` multiply-accumulates each at 2 FLOPs: the
scores again (its own recomputation: the forward keeps no ``L x L`` tensor, so the weights are made
anew from q, k and the saved log-sum-exp; counted here because the kernel performs it, and never in
``step_mfu_pct``, whose model FLOPs hold no recomputation), dP = dO·vᵀ, dV = pᵀ·dO, dQ = dS·k and
dK = dSᵀ·q. Elementwise work and masked lanes are not counted, as in the forward's file. Bytes: its
four operands and its result crossing HBM once.
"""

from benchmark import roofline

PRODUCTS = 5  # q·kᵀ again, dO·vᵀ, pᵀ·dO, dS·k, dSᵀ·q


def cost(operands, results) -> dict:
    (_, (batch, tokens, width3)) = operands[0]
    (_, (_, _, heads)) = operands[3]
    width = width3 // 3
    macs = PRODUCTS * batch * heads * tokens * tokens * (width // heads)
    return {"flops": 2.0 * macs, "bytes": roofline.array_bytes(operands + results), "matrix": True}
