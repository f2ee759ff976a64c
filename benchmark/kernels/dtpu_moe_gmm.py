"""``dtpu_moe_gmm`` (``distribuuuu_tpu/ops/grouped.gmm``): ``out[b] = rows[b] @ w[expert[b]]`` for rows
sorted by expert into blocks of one expert each, the weight tile read from the held array
``[held, K, N]`` (or ``[held, N, K]`` for the product with the transpose: the input gradient) by the
block's expert; two small int32 operands lead (the blocks' experts, the number of live blocks).

What a call costs at least, whatever the routing: its result written once, whole (a dead block's rows
are zeros, and still written). The products themselves, ``2·R·K·N`` FLOPs for the ``R`` rows handed
in, and the reads of rows and weights run for the live blocks only; how many are
live is the routing's and in no shape, so they cannot be priced from a call's shapes without pricing
work that is skipped: with the ceiling in ``flops`` the cell's first traced run read
``kernel_roofline_pct`` 104.5, 11 to 14 of its 25 blocks a round being dead (PERF.md section 6, PR 30).
So ``flops`` is 0 and ``bytes`` the result's: a floor of the call's time, under which the share reads
what the kernels take over writing their results alone. The expert layer's own share,
``moe_experts_roofline_pct``, prices the slots the program counted and is the one to read for it.
"""

from benchmark import roofline


def cost(operands, results) -> dict:
    return {"flops": 0.0, "bytes": roofline.array_bytes(results), "matrix": True}
