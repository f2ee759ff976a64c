"""``dtpu_window512_attn_fwd`` (``distribuuuu_tpu/ops/causal_attention.forward`` with ``window=512``):
sliding-window softmax attention in flash form, ``softmax(q·kᵀ / sqrt(dk), i - 512 < j <= i)·v`` for every
query head, one tile of query rows against one of key rows a grid step, only the tiles that meet the band;
writes the output ``[B, H, L, dv]`` and the rows' log-sum-exp ``[B, H, 1, L]``.

Operands as the compiled step holds them: queries ``q [B, H, L, dk]`` and each key head's keys and values
side by side ``kv [B, G, L, dk + dv]``.

Operations: the two matrix products of each query head over the band, ``Σ_i min(i + 1, 512)`` key
positions a head: scores at ``dk`` and weighted values at ``dv`` multiply-accumulates a position, 2 FLOPs
each. The masked parts of the tiles the band's edges cross, which the kernel computes and throws away, are
not counted, nor the softmax's elementwise work (the convention of ``roofline.py``: matrix work alone), so
the count is one forward pass of ``flops/laguna_s21.py``'s ``swa_scores`` + ``swa_values`` at its shapes and
the floor stays under the kernel's own work. Bytes: its operands and results crossing HBM once.
"""

from benchmark import roofline

WINDOW = 512


def band_positions(q) -> int:
    batch, heads, tokens, _ = q
    seen = min(tokens, WINDOW)
    return batch * heads * (seen * (seen + 1) // 2 + (tokens - seen) * WINDOW)


def widths(operands) -> tuple[tuple[int, ...], int, int]:
    """``(q's shape, dk, dv)`` from a call's operands: q and kv lead."""
    q, kv = operands[0][1], operands[1][1]
    return q, q[-1], kv[-1] - q[-1]


def cost(operands, results) -> dict:
    q, dk, dv = widths(operands)
    macs = band_positions(q) * (dk + dv)
    return {"flops": 2.0 * macs, "bytes": roofline.array_bytes(operands + results), "matrix": True}
