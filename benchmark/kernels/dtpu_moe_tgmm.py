"""``dtpu_moe_tgmm`` (``distribuuuu_tpu/ops/grouped.tgmm``): the held experts' weight gradient
``dw[e] = Σ lhs[b]ᵀ @ rhs[b]`` over the blocks of expert ``e``, float32 ``[held, K, N]``, summed in
VMEM over an expert's consecutive blocks and written once (zeros for an expert with no live block);
three small int32 operands lead (the walk over experts and blocks).

What a call costs at least, whatever the routing: its result written once. The products, ``2·R·K·N``
FLOPs for the ``R`` rows handed in (``lhs [R, K]``, ``rhs [R, N]``), and the reads of
both run for the live blocks only, whose number is in no shape: priced from the shapes they would
count work that is skipped and the share could pass 100 (see ``dtpu_moe_gmm.py``). So ``flops`` is 0
and ``bytes`` the result's: a floor.
"""

from benchmark import roofline


def cost(operands, results) -> dict:
    return {"flops": 0.0, "bytes": roofline.array_bytes(results), "matrix": True}
