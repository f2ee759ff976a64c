"""``dtpu_causal_attn_fwd`` (``distribuuuu_tpu/ops/causal_attention.forward``): causal softmax attention in
flash form, ``softmax((q·kᵀ + q_r·k_rᵀ) / sqrt(dk + dr), causal)·v`` for every query head, one tile of query
rows against one of key rows a grid step, only the tiles on or below the diagonal; writes the output
``[B, H, L, dv]`` and the rows' log-sum-exp ``[B, H, 1, L]``.

Operands as the compiled step holds them: queries ``q [B, H, L, dk + dr]``, each key head's keys and
values side by side ``kv [B, G, L, dk + dv]`` and, where every head shares a key part, the one shared key
``k_r [B, L, dr]`` (the call's only operand of three dimensions).

Operations: the two matrix products of each query head over the causal half, ``L·(L+1)/2`` key positions
a head: scores at ``dk + dr`` and weighted values at ``dv`` multiply-accumulates a position, 2 FLOPs each.
The masked half of the diagonal tiles, which the kernel computes and throws away, is not counted, nor the
softmax's elementwise work (the convention of ``roofline.py``: matrix work alone), so the count is one
forward pass of ``flops/kanana2_30b.py``'s ``mla_scores`` + ``mla_values`` at its shapes and the floor stays
under the kernel's own work. Bytes: its operands and results crossing HBM once.
"""

from benchmark import roofline


def widths(operands) -> tuple[tuple[int, ...], int, int]:
    """``(q's shape, dk + dr, dv)`` from a call's operands: q and kv lead, the shared key is the one operand
    of three dimensions."""
    q, kv = operands[0][1], operands[1][1]
    dr = next((shape[-1] for _, shape in operands if len(shape) == 3), 0)
    return q, q[-1], kv[-1] - (q[-1] - dr)


def causal_positions(q) -> int:
    batch, heads, tokens, _ = q
    return batch * heads * tokens * (tokens + 1) // 2


def cost(operands, results) -> dict:
    q, dq, dv = widths(operands)
    macs = causal_positions(q) * (dq + dv)
    return {"flops": 2.0 * macs, "bytes": roofline.array_bytes(operands + results), "matrix": True}
