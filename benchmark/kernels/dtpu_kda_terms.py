"""``dtpu_kda_terms`` (``distribuuuu_tpu/ops/kda_terms.forward``): the Kimi delta rule's within-chunk products for
``N`` (chunk, head) tiles, ``q``, ``k [N, Q, K]`` in the compute dtype and the log-decays' cumulative sums
``[N, Q, K]`` float32 in, ``P`` ``[N, Q, Q]`` float32 and ``W`` ``[N, Q, Q]`` out, the decays' factors through
each sub-chunk's first position formed in VMEM.

Operations: the products the kernel forms, float32 operands counted once at 2 FLOPs a multiply-accumulate (the
passes of bfloat16 pieces that make a float32 product on the matrix unit are the implementation's, not the
algorithm's, so the floor stays under every realisation): for each sub-chunk ``j`` of ``SUB`` rows, its rows of
keys and of queries, ``2·SUB x K``, against the keys up to its end, ``SUB·(j + 1) x K``. The factors' ``exp``
and products are vector work and not counted. Bytes: ``q``, ``k`` and the sums read, ``P`` and ``W`` written once.
"""

from benchmark import roofline

SUB = 16  # positions of a sub-chunk (distribuuuu_tpu/ops/kda_terms.SUB)


def sub_chunk_macs(chunk: int, width: int) -> int:
    """Multiply-accumulates of one tile's rows of keys and queries against the keys up to each sub-chunk's end."""
    subs = chunk // SUB
    return 2 * SUB * width * SUB * subs * (subs + 1) // 2


def cost(operands, results) -> dict:
    _, (_, (tiles, chunk, width)), _ = operands
    return {"flops": 2.0 * tiles * sub_chunk_macs(chunk, width), "bytes": roofline.array_bytes(operands + results),
            "matrix": True}
