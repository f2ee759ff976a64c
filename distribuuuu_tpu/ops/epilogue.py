"""Fused conv-epilogue — Pallas TPU kernels for the resnet hot blocks.

Every conv→BN→relu→add boundary XLA leaves as separate ``fusion`` ops
round-trips the conv output through HBM up to three times (BN read+write, add
read+write, relu); PERF.md §5 says where a resnet50 step's time goes on the
chip. These kernels fuse the whole epilogue — BN-apply (scale/shift from
running *or* batch stats), the optional residual add, and the ReLU — into one
VMEM-resident pass over the conv output: one HBM read of ``x`` (+ one of the
residual), one write of the block output.

The decomposition keeps BN *statistics* outside the kernel, exactly where
flax computes them (`models/layers.EpilogueBatchNorm`): batch-stat
reduction, the SyncBN ``pmean`` over the mesh's batch axes, and the running
EMA update are unchanged code, so SyncBN and ``MODEL.BN_DTYPE`` semantics
are preserved bit-for-bit. What the kernel receives is the per-channel
affine the stats resolve to — ``mean`` and ``mul = rsqrt(var+eps)·scale``
and ``bias``, the very quantities flax's ``_normalize`` folds to — applied
in the same operation order (subtract, multiply, add, cast) so the fused
output is bitwise the unfused path's.

Training support: both kernels are `jax.custom_vjp` whose backward
recomputes the *oracle formulation* with XLA and transposes through it
(the moe_kernel.py recompute pattern — the epilogue is cheaper to rebuild
than its intermediates are to save), so gradients are exactly the unfused
path's gradients; grads through the batch statistics flow through the
unchanged stats code outside the kernel.

Routing via `switch_epilogue`: an explicit argument, else the run's
``MODEL.FUSED_EPILOGUE`` (default off). Interpret-verified
(tests/test_epilogue.py) and compiled for the chip (chip_smoke.py); it has no
end-to-end verdict on the chip yet (PERF.md). The kernels compile through
Mosaic unless a caller asks for the Pallas interpreter (``interpret=True``, or
`ops.interpret.set_pallas_interpret` for the CPU test suite); the platform
never picks it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distribuuuu_tpu.ops.interpret import pallas_interpret
from distribuuuu_tpu.ops.vmem_guard import VmemBudgetGuard

# VMEM-budget guard (the ops/vmem_guard.py convention): each grid step holds
# the double-buffered x/residual/out tiles plus the f32 intermediate. Past
# the per-core budget the Mosaic compile fails opaquely inside whatever
# stack traced the model — estimate up front and fall back to the oracle
# formulation, which is numerically IDENTICAL by construction (it is the
# kernels' own backward), with one warning per shape.
_VMEM_GUARD = VmemBudgetGuard("DTPU_EPILOGUE_VMEM_BUDGET_MB")

# cfg.MODEL.FUSED_EPILOGUE lands here for the duration of a trainer run
# (trainer._model_globals_scoped restores it on return). Like the BN boundary
# dtype, the value is read at *trace* time — flipping it requires re-jitting.
_CFG_FUSED = False

_BLOCK_ROWS_DEFAULT = 256


def set_fused_epilogue_default(enabled: bool) -> None:
    global _CFG_FUSED
    _CFG_FUSED = bool(enabled)


def get_fused_epilogue_default() -> bool:
    return _CFG_FUSED


def switch_epilogue(fused: bool | None = None) -> bool:
    """``fused`` when given, else the run's ``MODEL.FUSED_EPILOGUE``."""
    return _CFG_FUSED if fused is None else bool(fused)


# ---------------------------------------------------------------------------
# Oracle: the unfused formulation, producing EXACTLY the fused outputs.
# Shared by the custom-VJP backward (XLA recompute), the VMEM-guard
# fallback, and the equality tests.
# ---------------------------------------------------------------------------

def oracle_epilogue(x, mean, mul, bias, identity=None, *, relu=True, bn_dtype):
    """The epilogue as flax composes it, term for term.

    ``y = (x − mean)·mul + bias`` follows `flax.linen.normalization
    ._normalize`'s operation order (subtract, multiply by the pre-folded
    ``rsqrt(var+eps)·scale``, add bias — all in f32 via promotion), cast to
    the BN boundary dtype, then the block code's ``(+ identity) → relu`` in
    the boundary dtype. Bitwise-identical to `nn.BatchNorm` + the unfused
    block sequence (pinned in tests/test_epilogue.py), which makes it a
    sound recompute backward AND a sound guard fallback.
    """
    y = x - mean  # x promotes to f32 against the f32 stats, as in flax
    y = y * mul
    y = y + bias
    y = y.astype(bn_dtype)
    if identity is not None:
        y = y + identity
    if relu:
        y = jax.nn.relu(y)
    return y


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _epilogue_kernel(*refs, relu: bool, bn_dtype, residual: bool):
    """One [T, C] row tile: affine(f32) → cast → (+residual) → relu.

    Purely elementwise per row, so the ragged last tile needs no masking:
    padded rows compute garbage that the output BlockSpec discards, and no
    reduction exists for them to poison.
    """
    if residual:
        x_ref, mean_ref, mul_ref, bias_ref, id_ref, o_ref = refs
    else:
        x_ref, mean_ref, mul_ref, bias_ref, o_ref = refs
    y = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * mul_ref[...]
    y = y + bias_ref[...]
    y = y.astype(bn_dtype)
    if residual:
        y = y + id_ref[...]
    if relu:
        y = jax.nn.relu(y)
    o_ref[...] = y.astype(o_ref.dtype)


def _epilogue_impl(x, mean, mul, bias, identity, relu, bn_dtype, block_rows, interpret):
    shape = x.shape
    c = shape[-1]
    r = int(np.prod(shape[:-1]))
    x2 = x.reshape(r, c)
    out_dtype = (
        jnp.result_type(bn_dtype, identity.dtype) if identity is not None else bn_dtype
    )
    t = min(int(block_rows), r)
    grid = pl.cdiv(r, t)
    args = [x2, mean.reshape(1, c), mul.reshape(1, c), bias.reshape(1, c)]
    in_specs = [
        pl.BlockSpec((t, c), lambda i: (i, 0)),
        pl.BlockSpec((1, c), lambda i: (0, 0)),
        pl.BlockSpec((1, c), lambda i: (0, 0)),
        pl.BlockSpec((1, c), lambda i: (0, 0)),
    ]
    if identity is not None:
        args.append(identity.reshape(r, c))
        in_specs.append(pl.BlockSpec((t, c), lambda i: (i, 0)))
    out = pl.pallas_call(
        functools.partial(
            _epilogue_kernel,
            relu=relu,
            bn_dtype=bn_dtype,
            residual=identity is not None,
        ),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((t, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(shape[:-1] + (c,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused_epilogue(x, mean, mul, bias, relu, bn_dtype, block_rows, interpret):
    return _epilogue_impl(x, mean, mul, bias, None, relu, bn_dtype, block_rows, interpret)


def _epilogue_fwd(x, mean, mul, bias, relu, bn_dtype, block_rows, interpret):
    return (
        _epilogue_impl(x, mean, mul, bias, None, relu, bn_dtype, block_rows, interpret),
        (x, mean, mul, bias),
    )


def _epilogue_bwd(relu, bn_dtype, block_rows, interpret, res, g):
    # XLA recompute: transpose through the oracle formulation, so gradients
    # are exactly the unfused path's (incl. the relu/cast masks)
    x, mean, mul, bias = res
    _, pull = jax.vjp(
        lambda x_, me, mu, bi: oracle_epilogue(
            x_, me, mu, bi, relu=relu, bn_dtype=bn_dtype
        ),
        x, mean, mul, bias,
    )
    return pull(g)


_fused_epilogue.defvjp(_epilogue_fwd, _epilogue_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused_epilogue_res(x, mean, mul, bias, identity, relu, bn_dtype, block_rows, interpret):
    return _epilogue_impl(
        x, mean, mul, bias, identity, relu, bn_dtype, block_rows, interpret
    )


def _epilogue_res_fwd(x, mean, mul, bias, identity, relu, bn_dtype, block_rows, interpret):
    return (
        _epilogue_impl(
            x, mean, mul, bias, identity, relu, bn_dtype, block_rows, interpret
        ),
        (x, mean, mul, bias, identity),
    )


def _epilogue_res_bwd(relu, bn_dtype, block_rows, interpret, res, g):
    x, mean, mul, bias, identity = res
    _, pull = jax.vjp(
        lambda x_, me, mu, bi, id_: oracle_epilogue(
            x_, me, mu, bi, id_, relu=relu, bn_dtype=bn_dtype
        ),
        x, mean, mul, bias, identity,
    )
    return pull(g)


_fused_epilogue_res.defvjp(_epilogue_res_fwd, _epilogue_res_bwd)


def _tile_vmem_bytes(t: int, c: int, x_item: int, id_item: int, out_item: int) -> int:
    """Per-grid-step estimate: double-buffered x/residual/out row tiles plus
    the f32 compute intermediates and the three per-channel vectors."""
    blocks = t * c * (x_item + id_item + out_item)
    intermediates = 2 * t * c * 4  # the f32 affine temp + one working copy
    small = 3 * c * 4
    return 2 * blocks + intermediates + small


def fused_conv_epilogue(
    x,
    mean,
    mul,
    bias,
    identity=None,
    *,
    relu: bool = True,
    bn_dtype,
    block_rows: int = _BLOCK_ROWS_DEFAULT,
    interpret: bool | None = None,
):
    """BN-apply → (+residual) → ReLU over a conv output, fused on TPU.

    ``x`` is the conv output ``[..., C]`` (any float dtype), ``mean``/
    ``mul``/``bias`` the per-channel f32 affine the BN's stats resolve to
    (``mul = rsqrt(var+eps)·scale`` — `EpilogueBatchNorm` folds them exactly
    as flax's ``_normalize`` does), ``identity`` the optional residual in
    the BN boundary dtype. Differentiable in all array arguments; the
    backward recomputes the oracle formulation with XLA, so gradients equal
    the unfused path's. A row tile too large for VMEM falls back to the
    numerically identical `oracle_epilogue` with a one-time warning instead
    of failing opaquely inside Mosaic.
    """
    if interpret is None:
        # traced from inside model code, where no caller can thread the flag
        # through flax: the process-wide request (ops/interpret.py), never
        # the platform
        interpret = pallas_interpret()
    c = int(x.shape[-1])
    r = int(np.prod(x.shape[:-1]))
    t = min(int(block_rows), r)
    out_dtype = (
        jnp.result_type(bn_dtype, identity.dtype) if identity is not None else bn_dtype
    )
    estimate = _tile_vmem_bytes(
        t,
        c,
        np.dtype(x.dtype).itemsize,
        np.dtype(identity.dtype).itemsize if identity is not None else 0,
        np.dtype(out_dtype).itemsize,
    )
    kind = "fused_conv_epilogue" + ("+res" if identity is not None else "")
    if not _VMEM_GUARD.within(
        kind,
        (kind, t, c, str(x.dtype)),
        estimate,
        f"falling back to the (numerically identical) unfused epilogue at "
        f"rows={t}, C={c}; shrink block_rows to refit the tile",
    ):
        return oracle_epilogue(
            x, mean, mul, bias, identity, relu=relu, bn_dtype=bn_dtype
        )
    if identity is None:
        return _fused_epilogue(
            x, mean, mul, bias, relu, bn_dtype, int(block_rows), interpret
        )
    return _fused_epilogue_res(
        x, mean, mul, bias, identity, relu, bn_dtype, int(block_rows), interpret
    )
