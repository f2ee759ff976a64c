"""``dtpu_kda_terms_bwd`` (``distribuuuu_tpu/ops/kda_terms.backward``): the backward pass of ``dtpu_kda_terms``,
``q``, ``k``, the log-decays' cumulative sums, ``dP`` float32 and ``dW`` in, ``dq``, ``dk`` and the sums' gradient
out; the factors formed again in VMEM.

Operations: for each sub-chunk, with ``G`` its ``2·SUB`` rows of ``dP`` and ``dW``, the two products of the
forward's shape, ``dR = G·C`` and ``dC = Gᵀ·R``, each counted once at 2 FLOPs a multiply-accumulate as in
``dtpu_kda_terms.py`` (twice its count); the factors and the gradients through them are vector work. Bytes: the
five operands read and the three results written once.
"""

from benchmark import files, roofline

dtpu_kda_terms = files.load_module("kernels", "dtpu_kda_terms")

PRODUCTS = 2  # G·C, Gᵀ·R


def cost(operands, results) -> dict:
    _, (_, (tiles, chunk, width)), *_ = operands
    return {"flops": 2.0 * PRODUCTS * tiles * dtpu_kda_terms.sub_chunk_macs(chunk, width),
            "bytes": roofline.array_bytes(operands + results), "matrix": True}
