"""``dtpu_causal_attn_bwd`` (``distribuuuu_tpu/ops/causal_attention.backward``): the gradient of
``dtpu_causal_attn_fwd`` in one kernel (FlashAttention-2's backward), the tiles on or below the diagonal a
key block's run at a time: dQ of a whole row in VMEM, dK and dV a run's tiles in VMEM.

Operands: the forward's (q, kv and, with a shared key part, k_r), the output's gradient
``dO [B, H, L, dv]`` and the rows' statistics ``[B, H, 2, L]`` float32 (the log-sum-exp and
``δ = rowsum(dO∘O)``). Results: dq, dkv a query head each and, with a shared part, dk_r a head each.

Operations: five matrix products a query head over the causal half, ``L·(L+1)/2`` key positions: the
scores again at ``dk + dr`` (its own recomputation: no ``L x L`` tensor is kept, so the weights are made
anew from q, k and the saved log-sum-exp; counted because the kernel performs it, and never in
``step_mfu_pct``, whose model FLOPs hold no recomputation), dP = dO·vᵀ and dV = pᵀ·dO at ``dv``, dQ = dS·k
and dK = dSᵀ·q at ``dk + dr``; 2 FLOPs a multiply-accumulate. The masked half of the diagonal tiles and
the elementwise work are not counted, as in the forward's file. Bytes: its operands and results crossing
HBM once.
"""

from benchmark import files, roofline

_forward = files.load_module("kernels", "dtpu_causal_attn_fwd")


def cost(operands, results) -> dict:
    q, dq, dv = _forward.widths(operands)
    macs = _forward.causal_positions(q) * (3 * dq + 2 * dv)
    return {"flops": 2.0 * macs, "bytes": roofline.array_bytes(operands + results), "matrix": True}
