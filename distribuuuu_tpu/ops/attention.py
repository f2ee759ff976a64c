"""Fused attention kernels (Pallas TPU) with their XLA formulations.

Three families, sorted apart on purpose:

**Causal softmax attention** (the token models; near the bottom of this file): `causal_attention` is the
one entry point of grouped-query heads (`self_attention(causal=True)`, packed) and of latent attention's
heads (`latent_causal_attention`: keys wider than values, a key part all heads share). It takes the flash
pair of `ops/causal_attention.py` (`dtpu_causal_attn_fwd`, `dtpu_causal_attn_bwd`: the scores in VMEM,
the causal half only) where the step is traced for TPUs at shapes the pair tiles, and XLA's blocks
(`xla_causal_core`) elsewhere; device and shape decide, nothing else.

**Bias-free self-attention from packed qkv** (ViT, MAE; bottom of this file):
`self_attention` is what `models/vit.py` calls. It takes one fused forward
kernel and one fused backward kernel (`dtpu_attn_fwd`, `dtpu_attn_bwd`)
where the step is being traced for TPUs and a batch row's tile fits VMEM,
and the plain einsums elsewhere; device and shape decide, nothing else. On
the v5e the pair took `vit_b16.train`'s twelve ``block*/attn`` from 109.9 to
42.0 ms a step (PERF.md section 6, PR 27).

**Biased attention for BoTNet's MHSA** (reference
`/root/reference/distribuuuu/models/botnet.py:193-215`):
``softmax(q·kᵀ + pos_bias)·v`` over L = H·W ≈ 196 tokens, one (batch, head)
tile a grid step, opt-in through ``MHSA(fuse=True)`` (off by default). What
these kernels pay that the bias-free pair does not: a float32 ``[L, L]`` bias
read from HBM per (batch, head) (as many bytes as one of the passes they save; the ``abs`` variant forms it
in-kernel from the ``[L, D]`` table instead), q, k and v relaid to
``[B·H, L, d]`` before the call, and a backward that is XLA einsums in
float32 (`_bwd`), which writes every L×L tensor back to HBM. An earlier
docstring carried a "0.77x against XLA, 1545 vs 1834 img/s" verdict dated
2026-07-31; no ledger line, journal or `PERF.md` entry records that run, so
it is not repeated here: the kernels are off until a benchmark cell
(`botnet50.train`, PERF.md section 7) gives them a verdict. Past the
single-tile VMEM budget the dispatch re-tiles to the BLOCKWISE
online-softmax kernels below (O(block²) per tile), so L≥1024 runs in-kernel
instead of falling back.

Training support of the biased family: `fused_attention` is a
`jax.custom_vjp`. The forward is the Pallas kernel; the backward recomputes
the attention weights with XLA einsums and emits standard gradients.

The biased kernels run per (batch·head) grid step; tiles (L ≤ a few hundred,
D=128) fit VMEM comfortably: q/k/v bf16 196×128 ≈ 50 KB each, bias/logits
f32 196×196 ≈ 154 KB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops import causal_attention as causal_attention_kernels
from distribuuuu_tpu.ops.causal_attention import nn, nt, tn
from distribuuuu_tpu.ops.interpret import pallas_interpret
from distribuuuu_tpu.ops.rows import rows_in_groups
from distribuuuu_tpu.ops.vmem_guard import DEFAULT_VMEM_BUDGET_MB, VmemBudgetGuard

# VMEM-budget guard: the single-tile kernels keep a whole (batch·head) tile
# resident, so per-tile footprint grows O(L²) — past ~16 MB/core the Mosaic
# compile fails with an opaque allocation error deep in the serve/train
# stack. Past the single-tile budget the dispatch RE-TILES to the blockwise
# (flash-style online-softmax) kernels below, whose per-tile footprint is
# O(block²) — so L=1024+ runs in-kernel instead of falling back (the large-L
# regime the kernel was kept for, docs/PERFORMANCE.md). Only when no block
# size divides L does the guard count a fallback to the XLA path, with ONE
# warning per shape.
_VMEM_GUARD = VmemBudgetGuard("DTPU_ATTN_VMEM_BUDGET_MB")

# Blockwise tile bounds: blocks are divisors of L (padding a remainder
# block would complicate the bias tiling), sublane-aligned (multiples of 8
# — Mosaic tiles f32 as (8, 128)), and capped at 512 so the per-tile
# softmax intermediates stay small. Divisor-based, not a fixed candidate
# list: the patch-grid token counts this exists for (784 at 448px/16 →
# block 392, 1024 → block 512) are not all powers of two.
_BLOCK_MAX = 512
_BLOCK_ALIGN = 8


def _tile_vmem_bytes(l: int, d: int, dv: int, itemsize: int, bias_input: bool) -> int:
    """Single-tile VMEM estimate: in/out blocks double-buffered by the grid
    pipeline, plus the f32 [L, L] logits/exp intermediates the softmax holds."""
    inputs = 2 * l * d * itemsize + l * dv * itemsize  # q, k, v tiles
    inputs += l * l * 4 if bias_input else l * d * itemsize  # bias | emb table
    output = l * dv * itemsize
    intermediates = 2 * l * l * 4  # logits + exp, f32
    return 2 * (inputs + output) + intermediates


def _tile_vmem_bytes_blockwise(
    bq: int, bk: int, d: int, dv: int, itemsize: int, bias_input: bool
) -> int:
    """Blockwise-tile VMEM estimate: the softmax intermediates are priced at
    the [bq, bk] BLOCK, not the full [L, L] — the fix for the guard's
    over-refusal at large L (it used to price full f32 L² and refuse shapes
    the re-tiled kernel runs comfortably)."""
    inputs = bq * d * itemsize + bk * d * itemsize + bk * dv * itemsize
    inputs += bq * bk * 4 if bias_input else bk * d * itemsize  # bias | emb blk
    # f32 accumulator + the m/l online-softmax rows, revisited across k steps
    outputs = bq * dv * 4 + 2 * bq * 4
    intermediates = 2 * bq * bk * 4  # s + exp(s), f32
    return 2 * (inputs + outputs) + intermediates


def _pick_block(l: int, d: int, dv: int, itemsize: int, bias_input: bool):
    """Block size for the blockwise re-tile: the largest sublane-aligned
    divisor of L (≥2 blocks, ≤ _BLOCK_MAX) whose blockwise estimate fits the
    budget; None when the shape can't re-tile (→ XLA fallback)."""
    budget = _VMEM_GUARD.budget_bytes()
    start = min(_BLOCK_MAX, l // 2)
    start -= start % _BLOCK_ALIGN  # walk aligned values only
    for b in range(start, _BLOCK_ALIGN - 1, -_BLOCK_ALIGN):
        if l % b == 0:
            if _tile_vmem_bytes_blockwise(b, b, d, dv, itemsize, bias_input) <= budget:
                return b
    return None


def _within_vmem_budget(kind: str, l: int, d: int, dv: int, itemsize: int,
                        bias_input: bool) -> bool:
    return _VMEM_GUARD.within(
        kind,
        (kind, l, d, dv, itemsize),
        _tile_vmem_bytes(l, d, dv, itemsize, bias_input),
        f"falling back to xla_attention at L={l}",
    )


def xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, bias: jnp.ndarray):
    """Reference path: plain einsums (q pre-scaled; bias = position logits).

    The QK contraction asks for an f32 result (preferred_element_type) so the
    MXU accumulates in f32 — under bf16 inputs the old post-hoc
    ``logits.astype(f32)`` upcast happened AFTER the accumulation had already
    rounded (DT104), while the pallas kernel below always accumulated f32:
    the two paths disagreed in exactly the low bits the softmax max-subtract
    is most sensitive to.
    """
    logits = (
        jnp.einsum("bnxd,bnyd->bnxy", q, k, preferred_element_type=jnp.float32)
        + bias
    )
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bnxy,bnyd->bnxd", weights, v)


def _attn_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref):
    """One (batch·head) tile: logits → +bias → softmax(f32) → weighted sum."""
    q = q_ref[0]  # [L, D]
    k = k_ref[0]
    v = v_ref[0]
    bias = bias_ref[0]  # [L, L] float32
    logits = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        + bias
    )
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    o_ref[0] = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _fused_fwd_impl(q, k, v, bias, *, interpret: bool = False):
    b, n, l, d = q.shape
    dv = v.shape[-1]  # dim_v may differ from dim_qk (MHSA exposes both)
    qf = q.reshape(b * n, l, d)
    kf = k.reshape(b * n, l, d)
    vf = v.reshape(b * n, l, dv)
    bf = bias.astype(jnp.float32).reshape(b * n, l, l)
    out = pl.pallas_call(
        _attn_kernel,
        grid=(b * n,),
        in_specs=[
            pl.BlockSpec((1, l, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, dv), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, l), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, l, dv), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * n, l, dv), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, bf)
    return out.reshape(b, n, l, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_attention(q, k, v, bias, interpret=False):
    return _fused_fwd_impl(q, k, v, bias, interpret=interpret)


def _fwd(q, k, v, bias, interpret):
    return _fused_fwd_impl(q, k, v, bias, interpret=interpret), (q, k, v, bias)


def _bwd(interpret, res, g):
    q, k, v, bias = res
    # recompute weights (XLA): standard attention gradients. f32 accumulation
    # on the contraction itself (not a post-hoc astype): the recomputed
    # weights must match the f32-accumulated forward or the VJP is biased.
    logits = jnp.einsum(
        "bnxd,bnyd->bnxy", q, k, preferred_element_type=jnp.float32
    ) + bias.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    g32 = g.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    dp = jnp.einsum("bnxd,bnyd->bnxy", g32, v32)
    dv = jnp.einsum("bnxy,bnxd->bnyd", p, g32).astype(v.dtype)
    dsoft = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bnxy,bnyd->bnxd", dsoft, k.astype(jnp.float32)).astype(q.dtype)
    dk = jnp.einsum("bnxy,bnxd->bnyd", dsoft, q.astype(jnp.float32)).astype(k.dtype)
    dbias = dsoft.astype(bias.dtype)
    return dq, dk, dv, dbias


_fused_attention.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) variant: the large-L re-tiling
# ---------------------------------------------------------------------------
#
# Grid (batch·head, q-block, k-block) with the k dimension innermost: TPU
# grids execute sequentially, so the f32 accumulator and the online-softmax
# m/l rows live in revisited output blocks (their index maps ignore ki) and
# carry across k steps. Per-tile footprint is O(block²) where the single-tile
# kernel is O(L²) — at L=1024 the single-tile estimate blows the 12 MB budget
# ~20x while a 512-block tile fits with room to spare. The backward is the
# same XLA flash-style recompute as the single-tile kernels (math-identical
# logits, so one VJP serves both tilings).


def _attn_kernel_blk(q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref):
    """One (bn, q-block, k-block) step: online-softmax accumulate in f32."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    q = q_ref[0]  # [bq, D]
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]  # [bk, Dv]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) + bias_ref[0]
    m_prev = m_ref[0]  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)  # first step: exp(-inf - finite) = 0
    l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = o_ref[0] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[0] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = o_ref[0] / l_ref[0]


def _attn_kernel_abs_blk(q_ref, k_ref, v_ref, emb_ref, o_ref, m_ref, l_ref):
    """Blockwise abs variant: the bias block is q·emb_blkᵀ, formed in-kernel
    from the [bk, D] slice of the shared table (never materialized in HBM)."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    emb = emb_ref[...]  # [bk, D] block of the table
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        q, emb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = o_ref[0] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[0] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = o_ref[0] / l_ref[0]


def _fused_fwd_blk_impl(q, k, v, bias_or_emb, block, *, abs_table: bool,
                        interpret: bool = False):
    b, n, l, d = q.shape
    dv = v.shape[-1]
    nq = nk = l // block
    qf = q.reshape(b * n, l, d)
    kf = k.reshape(b * n, l, d)
    vf = v.reshape(b * n, l, dv)
    if abs_table:
        kernel = _attn_kernel_abs_blk
        last_in = bias_or_emb.astype(q.dtype)  # [L, D] table
        last_spec = pl.BlockSpec((block, d), lambda i, qi, ki: (ki, 0))
    else:
        kernel = _attn_kernel_blk
        last_in = bias_or_emb.astype(jnp.float32).reshape(b * n, l, l)
        last_spec = pl.BlockSpec((1, block, block), lambda i, qi, ki: (i, qi, ki))
    out, _, _ = pl.pallas_call(
        kernel,
        grid=(b * n, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, block, d), lambda i, qi, ki: (i, ki, 0)),
            pl.BlockSpec((1, block, dv), lambda i, qi, ki: (i, ki, 0)),
            last_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block, dv), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, block, 1), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, block, 1), lambda i, qi, ki: (i, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * n, l, dv), jnp.float32),
            jax.ShapeDtypeStruct((b * n, l, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * n, l, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, last_in)
    return out.astype(q.dtype).reshape(b, n, l, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_attention_blk(q, k, v, bias, block, interpret=False):
    return _fused_fwd_blk_impl(q, k, v, bias, block, abs_table=False,
                               interpret=interpret)


def _blk_fwd(q, k, v, bias, block, interpret):
    out = _fused_fwd_blk_impl(q, k, v, bias, block, abs_table=False,
                              interpret=interpret)
    return out, (q, k, v, bias)


def _blk_bwd(block, interpret, res, g):
    return _bwd(interpret, res, g)  # identical logits → identical gradients


_fused_attention_blk.defvjp(_blk_fwd, _blk_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_attention_abs_blk(q, k, v, emb, block, interpret=False):
    return _fused_fwd_blk_impl(q, k, v, emb, block, abs_table=True,
                               interpret=interpret)


def _abs_blk_fwd(q, k, v, emb, block, interpret):
    out = _fused_fwd_blk_impl(q, k, v, emb, block, abs_table=True,
                              interpret=interpret)
    return out, (q, k, v, emb)


def _abs_blk_bwd(block, interpret, res, g):
    return _abs_bwd(interpret, res, g)


_fused_attention_abs_blk.defvjp(_abs_blk_fwd, _abs_blk_bwd)


def fused_attention(q, k, v, bias, *, interpret: bool = False):
    """softmax(q·kᵀ + bias)·v, fused on TPU; differentiable.

    q is expected pre-scaled (matching the reference, `botnet.py:205`).
    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU
    tests). Dispatch by VMEM footprint: the single-tile kernel where the
    whole (batch·head) tile fits the budget (the measured small-L path,
    unchanged), the blockwise online-softmax kernel where it doesn't but a
    block size divides L (the large-L regime — L=1024 fits the default
    12 MB budget re-tiled), and the XLA path — with a one-time warning —
    only when no tiling works.
    """
    l, d = q.shape[-2], q.shape[-1]
    dv, itemsize = v.shape[-1], np.dtype(q.dtype).itemsize
    if _tile_vmem_bytes(l, d, dv, itemsize, True) <= _VMEM_GUARD.budget_bytes():
        return _fused_attention(q, k, v, bias, interpret)
    block = _pick_block(l, d, dv, itemsize, True)
    if block is not None:
        return _fused_attention_blk(q, k, v, bias, block, interpret)
    _within_vmem_budget("fused_attention", l, d, dv, itemsize, bias_input=True)
    return xla_attention(q, k, v, bias)


# ---------------------------------------------------------------------------
# Absolute-position variant: bias computed IN-KERNEL from the shared table
# ---------------------------------------------------------------------------
#
# BoTNet's default (abs) position bias is ``q·embᵀ`` with one [L, D] table
# shared by every batch element and head (`models/botnet.py::AbsPosEmb`).
# Passing the *product* to the kernel makes XLA materialize a [B,N,L,L]
# float32 bias in HBM that the kernel immediately re-reads — at production
# shapes (B·N=1024 tiles, L=196) that is ~300 MB of pure round-trip per
# forward. Here the kernel takes the 100 KB table instead and computes the
# bias tile on the MXU while everything is VMEM-resident.


def _attn_kernel_abs(q_ref, k_ref, v_ref, emb_ref, o_ref):
    """One (batch·head) tile: q·kᵀ + q·embᵀ → softmax(f32) → weighted sum."""
    q = q_ref[0]  # [L, D]
    k = k_ref[0]
    v = v_ref[0]
    emb = emb_ref[...]  # [L, D], same block for every grid step
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        q, emb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    o_ref[0] = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _fused_abs_fwd_impl(q, k, v, emb, *, interpret: bool = False):
    b, n, l, d = q.shape
    dv = v.shape[-1]
    qf = q.reshape(b * n, l, d)
    kf = k.reshape(b * n, l, d)
    vf = v.reshape(b * n, l, dv)
    embf = emb.astype(q.dtype)  # [L, D]
    out = pl.pallas_call(
        _attn_kernel_abs,
        grid=(b * n,),
        in_specs=[
            pl.BlockSpec((1, l, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, dv), lambda i: (i, 0, 0)),
            pl.BlockSpec((l, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, l, dv), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * n, l, dv), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, embf)
    return out.reshape(b, n, l, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_attention_abs(q, k, v, emb, interpret=False):
    return _fused_abs_fwd_impl(q, k, v, emb, interpret=interpret)


def _abs_fwd(q, k, v, emb, interpret):
    return _fused_abs_fwd_impl(q, k, v, emb, interpret=interpret), (q, k, v, emb)


def _abs_bwd(interpret, res, g):
    q, k, v, emb = res
    # recompute logits (XLA, flash-style): standard attention gradients plus
    # the table path — bias = q·embᵀ, so dq += dsoft·emb and
    # demb = Σ_{b,n} dsoftᵀ·q
    q32, k32, e32 = (t.astype(jnp.float32) for t in (q, k, emb))
    logits = jnp.einsum("bnxd,bnyd->bnxy", q32, k32) + jnp.einsum(
        "bnxd,jd->bnxj", q32, e32
    )
    p = jax.nn.softmax(logits, axis=-1)
    g32 = g.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    dp = jnp.einsum("bnxd,bnyd->bnxy", g32, v32)
    dv = jnp.einsum("bnxy,bnxd->bnyd", p, g32).astype(v.dtype)
    dsoft = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = (
        jnp.einsum("bnxy,bnyd->bnxd", dsoft, k32)
        + jnp.einsum("bnxj,jd->bnxd", dsoft, e32)
    ).astype(q.dtype)
    dk = jnp.einsum("bnxy,bnxd->bnyd", dsoft, q32).astype(k.dtype)
    demb = jnp.einsum("bnxj,bnxd->jd", dsoft, q32).astype(emb.dtype)
    return dq, dk, dv, demb


_fused_attention_abs.defvjp(_abs_fwd, _abs_bwd)


def fused_attention_abs(q, k, v, emb, *, interpret: bool = False):
    """softmax(q·kᵀ + q·embᵀ)·v with the [L, D] position table applied
    in-kernel; differentiable (incl. d/d emb). q pre-scaled, as above.
    Dispatch mirrors `fused_attention`: single-tile → blockwise (the bias
    block is formed from the table slice in-kernel, so large L never
    materializes the [B, N, L, L] product) → XLA composition — which DOES
    materialize that product, but runs (the one-time warning says what it
    costs)."""
    l, d = q.shape[-2], q.shape[-1]
    dv, itemsize = v.shape[-1], np.dtype(q.dtype).itemsize
    if _tile_vmem_bytes(l, d, dv, itemsize, False) <= _VMEM_GUARD.budget_bytes():
        return _fused_attention_abs(q, k, v, emb, interpret)
    block = _pick_block(l, d, dv, itemsize, False)
    if block is not None:
        return _fused_attention_abs_blk(q, k, v, emb, block, interpret)
    _within_vmem_budget("fused_attention_abs", l, d, dv, itemsize, bias_input=False)
    return xla_attention(
        q, k, v,
        jnp.einsum(
            "bnid,jd->bnij", q, emb.astype(q.dtype),
            preferred_element_type=jnp.float32,
        ),
    )


# ---------------------------------------------------------------------------
# Bias-free self-attention from packed qkv (ViT/MAE): fused forward + backward
# ---------------------------------------------------------------------------
#
# The ViT block's attention has no bias, so nothing of size L×L has to exist
# outside VMEM in either direction. One grid step holds one batch row with all
# its heads: the packed ``[L, 3·D]`` row of the qkv Dense comes in as it lies
# (q, k and v are its 128-lane column groups, so no ``[B,H,L,hd]`` relayout is
# ever made) and the output leaves as the ``[L, D]`` row the proj Dense reads.
# A 128-lane group holds ``128 // hd`` heads. One head is picked out of a
# group by zeroing the other heads' lanes of ONE operand, which costs the MXU
# nothing (a 64-deep contraction fills half of its 128 rows either way) and
# needs no lane slicing: q masked gives that head's scores, the incoming
# gradient masked gives its dP and dV, k and q masked give dQ and dK, each
# landing in that head's lanes of a full-width result.
#
# The arithmetic is the einsum route's: operands in the input dtype, float32
# accumulation, scores and softmax in float32 (max-subtracted; the row sum is
# divided exactly, once per row), weights cast to the value dtype. Saved for
# the way back beside qkv and the output: the row log-sum-exp, ``[B, L, H]``.


def _packed_tile_vmem_bytes(l: int, d_model: int, num_heads: int, itemsize: int) -> int:
    """VMEM footprint of one grid step of the BACKWARD kernel (the larger of
    the pair): its blocks (qkv, d_qkv, out, d_out, lse), double-buffered by
    the grid pipeline, the three float32 ``[L, 128]`` accumulators and the
    float32 ``[L, L]`` intermediates one head keeps alive (scores, weights,
    dP, dS and the two casts: priced as six), all as the chip tiles them."""
    rows, lanes = pl.cdiv(l, 16) * 16, pl.cdiv(l, 128) * 128
    blocks = rows * (3 + 3 + 1 + 1) * d_model * itemsize + rows * pl.cdiv(num_heads, 128) * 128 * 4
    return 2 * blocks + 3 * rows * 128 * 4 + 6 * rows * lanes * 4


def _head_masks(head_dim: int):
    """Lane masks ``[1, 128]`` of the heads that share a 128-lane group (one
    ``None`` where a head fills the group)."""
    per = 128 // head_dim
    if per == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return [(lane >= j * head_dim) & (lane < (j + 1) * head_dim) for j in range(per)]


def _keep(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _cols(part: int, group: int, d_model: int) -> slice:
    """Lanes of 128-lane group ``group`` of q (part 0), k (1) or v (2) in a
    packed ``[L, 3·D]`` row."""
    start = part * d_model + group * 128
    return slice(start, start + 128)


def _self_attn_fwd_kernel(qkv_ref, o_ref, lse_ref, *, num_heads: int, head_dim: int):
    d_model = num_heads * head_dim
    scale = head_dim**-0.5
    masks = _head_masks(head_dim)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, num_heads), 1)
    lse_all = jnp.zeros(lse_ref.shape[1:], jnp.float32)
    for g in range(d_model // 128):
        q, k, v = (qkv_ref[0, :, _cols(part, g, d_model)] for part in range(3))
        out = None
        for j, mask in enumerate(masks):
            s = nt(_keep(mask, q), k) * scale  # [L, L] float32
            m = jnp.max(s, axis=-1, keepdims=True)
            e = jnp.exp(s - m)
            l = jnp.sum(e, axis=-1, keepdims=True)
            p = (e * (1.0 / l)).astype(v.dtype)
            o = nn(p, v)  # [L, 128]: this head's lanes hold its output
            out = o if out is None else jnp.where(mask, o, out)
            lse_all = jnp.where(head_lane == g * len(masks) + j, m + jnp.log(l), lse_all)
        o_ref[0, :, _cols(0, g, d_model)] = out.astype(o_ref.dtype)
    lse_ref[0] = lse_all


def _self_attn_bwd_kernel(
    qkv_ref, o_ref, do_ref, lse_ref, dqkv_ref, *, num_heads: int, head_dim: int
):
    d_model = num_heads * head_dim
    scale = head_dim**-0.5
    masks = _head_masks(head_dim)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, num_heads), 1)
    lse_all = lse_ref[0]
    for g in range(d_model // 128):
        q, k, v = (qkv_ref[0, :, _cols(part, g, d_model)] for part in range(3))
        do = do_ref[0, :, _cols(0, g, d_model)]
        # rowsum(dO ∘ O) per head = rowsum(P ∘ dP): the softmax backward's row term
        row = do.astype(jnp.float32) * o_ref[0, :, _cols(0, g, d_model)].astype(jnp.float32)
        dq = dk = dv = jnp.zeros(q.shape, jnp.float32)
        for j, mask in enumerate(masks):
            head = head_lane == g * len(masks) + j
            lse = jnp.sum(jnp.where(head, lse_all, 0.0), axis=-1, keepdims=True)
            qj, doj = _keep(mask, q), _keep(mask, do)
            p = jnp.exp(nt(qj, k) * scale - lse)  # the forward's weights, float32
            dp = nt(doj, v)
            delta = jnp.sum(_keep(mask, row), axis=-1, keepdims=True)
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            dv = dv + tn(p.astype(v.dtype), doj)
            dq = dq + nn(ds, _keep(mask, k))
            dk = dk + tn(ds, qj)
        for part, grad in enumerate((dq, dk, dv)):
            dqkv_ref[0, :, _cols(part, g, d_model)] = grad.astype(dqkv_ref.dtype)


def _row_spec(l: int, width: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, l, width), lambda i: (i, 0, 0))


# jitted so that the twelve blocks of a model share one traced kernel a shape
@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
def _self_attn_fwd_call(qkv, *, num_heads: int, interpret: bool):
    b, l, d3 = qkv.shape
    d_model = d3 // 3
    kernel = functools.partial(
        _self_attn_fwd_kernel, num_heads=num_heads, head_dim=d_model // num_heads
    )
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[_row_spec(l, d3)],
        out_specs=[_row_spec(l, d_model), _row_spec(l, num_heads)],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, d_model), qkv.dtype),
            jax.ShapeDtypeStruct((b, l, num_heads), jnp.float32),
        ],
        name="dtpu_attn_fwd",
        interpret=interpret,
    )(qkv)


@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
def _self_attn_bwd_call(qkv, out, d_out, lse, *, num_heads: int, interpret: bool):
    b, l, d3 = qkv.shape
    d_model = d3 // 3
    kernel = functools.partial(
        _self_attn_bwd_kernel, num_heads=num_heads, head_dim=d_model // num_heads
    )
    row, heads = _row_spec(l, d_model), _row_spec(l, num_heads)
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[_row_spec(l, d3), row, row, heads],
        out_specs=_row_spec(l, d3),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        name="dtpu_attn_bwd",
        interpret=interpret,
    )(qkv, out, d_out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fused_self_attention(qkv, num_heads: int, interpret: bool = False):
    """softmax(q·kᵀ/√hd)·v for every head of packed ``qkv [B, L, 3·H·hd]``
    (columns ordered (3, H, hd), as the qkv Dense leaves them) → ``[B, L,
    H·hd]``; differentiable, with one packed ``d_qkv``. Needs ``hd`` to
    divide 128 and ``H·hd`` to be a multiple of 128 (`self_attention`
    checks, and takes the einsums otherwise)."""
    return _self_attn_fwd_call(qkv, num_heads=num_heads, interpret=interpret)[0]


def _self_attn_fwd(qkv, num_heads, interpret):
    out, lse = _self_attn_fwd_call(qkv, num_heads=num_heads, interpret=interpret)
    return out, (qkv, out, lse)


def _self_attn_bwd(num_heads, interpret, res, d_out):
    qkv, out, lse = res
    return (_self_attn_bwd_call(qkv, out, d_out, lse, num_heads=num_heads, interpret=interpret),)


fused_self_attention.defvjp(_self_attn_fwd, _self_attn_bwd)


def xla_self_attention(qkv, num_heads: int):
    """The einsum route, as `models/vit.py` had it: four XLA ops whose
    float32 ``[B, H, L, L]`` scores and weights go through HBM."""
    b, l, d3 = qkv.shape
    head_dim = d3 // 3 // num_heads
    qkv = qkv.reshape(b, l, 3, num_heads, head_dim)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))  # [B,H,L,hd]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    w = jax.nn.softmax(s * head_dim**-0.5, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).reshape(b, l, d3 // 3)


#: `jax.monitoring` events of `self_attention`, one per call traced for a
#: mesh; the journal's ``counters`` records carry them (obs/monitors.py)
FUSED_CALLS_EVENT = "attn_fused_calls"
XLA_CALLS_EVENT = "attn_xla_calls"


def self_attention_fuses(
    device_kind: str, l: int, num_heads: int, head_dim: int, itemsize: int
) -> bool:
    """The route of `self_attention`, from what it can observe: the fused
    pair where the program is being traced for TPUs and one batch row's tile
    fits VMEM in a geometry the kernels tile (heads that divide a 128-lane
    group, a width of whole groups); the einsums everywhere else."""
    d_model = num_heads * head_dim
    return (
        device_kind.upper().startswith("TPU")
        and 128 % head_dim == 0
        and d_model % 128 == 0
        and _packed_tile_vmem_bytes(l, d_model, num_heads, itemsize)
        <= DEFAULT_VMEM_BUDGET_MB * 2**20
    )


#: query rows a block of `xla_causal_core`: the float32 scores of one block against its keys are what goes
#: through HBM at a time
CAUSAL_BLOCK = 1024
#: `jax.monitoring` events of `causal_attention`, one per call traced for a mesh: which route it took. The
#: journal's ``counters`` records carry them (obs/monitors.py)
CAUSAL_FUSED_EVENT = "causal_attn_fused_calls"
CAUSAL_XLA_EVENT = "causal_attn_xla_calls"
#: what `causal_attention` names for a layer checkpoint, on either route: its output, and on the kernels' route
#: the rows' log-sum-exp, the one other value their backward reads that is not an input. A model whose
#: checkpoint keeps both never runs the core's forward a second time (`models/deepseek_v3.KEPT`)
CAUSAL_OUT = "causal_attn_out"
CAUSAL_LSE = "causal_attn_lse"


def _causal_block(q, k, v, start: int):
    """One block of queries ``q [B, Q, G, R, hd]`` (rows ``start …``) against
    the keys ``[B, K, G, hd]`` and values ``[B, K, G, dv]`` of rows
    ``0 … start + Q - 1``; the scale is that of the query/key width."""
    head_dim = q.shape[-1]
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, preferred_element_type=jnp.float32)
    rows = start + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(jnp.arange(k.shape[1])[None, :] <= rows, s * head_dim**-0.5, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)  # row r always sees key 0: no empty row
    return jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(v.dtype), v)


def _causal_blocks(q, k, v, block: int):
    """``q [B, L, G, R, hd]`` against ``k [B, L, G, hd]``, ``v [B, L, G, dv]`` → ``[B, L, G, R, dv]``, for
    rows whose blocks' scores all stand at once."""
    one_block = jax.checkpoint(_causal_block, static_argnums=(3,))
    out = [
        one_block(q[:, start:start + block], k[:, :start + block], v[:, :start + block], start)
        for start in range(0, q.shape[1], block)
    ]
    return jnp.concatenate(out, axis=1)


def xla_causal_core(q, k, v, k_shared=None, block: int = CAUSAL_BLOCK):
    """`causal_attention` in XLA: blocks of `CAUSAL_BLOCK` query rows, each against the keys up to its own
    last row only, so the products above the diagonal are never formed (half of them at large L) and no
    ``L x L`` tensor exists; each block is rematerialised in the backward pass, so what is kept for it is q,
    k, v. A shared key part is copied to every key head, for the rows of one group at a time. The rows go
    through `ops.rows.rows_in_groups` by the last block's float32 scores (32 heads at 8192 keys: 1 GiB a
    row, so a row at a time there). The route off the chip and the kernels' reference in the tests."""
    _, l, heads, width = q.shape
    groups = k.shape[2]
    score_bytes = 4 * heads * min(block, l) * l

    def of_rows(q, k, v, *shared):
        if shared:
            k = jnp.concatenate([k, jnp.broadcast_to(shared[0][:, :, None, :], (*k.shape[:-1], shared[0].shape[-1]))],
                                axis=-1)
        rows = q.shape[0]
        out = _causal_blocks(q.reshape(rows, l, groups, heads // groups, width), k, v, block)
        return out.reshape(rows, l, heads, v.shape[-1])

    operands = (q, k, v) if k_shared is None else (q, k, v, k_shared)
    return rows_in_groups(of_rows, operands, score_bytes)


def xla_causal_attention(qkv, num_heads: int, kv_heads: int, block: int = CAUSAL_BLOCK):
    """`xla_causal_core` over packed ``qkv [B, L, (H + 2·G)·hd]`` (``H`` query heads, then ``G`` key heads,
    then ``G`` value heads, all of one width; query heads ``g·H/G …`` read key/value head ``g``) →
    ``[B, L, H·hd]``."""
    q, k, v = _unpacked(qkv, num_heads, kv_heads)
    out = xla_causal_core(q, k, v, block=block)
    return out.reshape(*out.shape[:2], -1)


def _unpacked(qkv, num_heads: int, kv_heads: int):
    b, l, width = qkv.shape
    hd = width // (num_heads + 2 * kv_heads)
    q, k, v = jnp.split(qkv, (num_heads * hd, (num_heads + kv_heads) * hd), axis=-1)
    return q.reshape(b, l, num_heads, hd), k.reshape(b, l, kv_heads, hd), v.reshape(b, l, kv_heads, hd)


def _heads_first(x):
    """``[B, L, N, d]`` <-> ``[B, N, L, d]``."""
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash_causal(q, k, v, k_shared, interpret: bool):
    return _flash_causal_fwd(q, k, v, k_shared, interpret)[0]


def _flash_causal_fwd(q, k, v, k_shared, interpret):
    with step_scope("causal_attn"):  # the rules take the scope themselves, as ops/grouped.py's do
        kv = _heads_first(jnp.concatenate([k, v], axis=-1))  # a group's keys and values side by side: one operand
        out, lse = causal_attention_kernels.forward(_heads_first(q), kv, k_shared, interpret=interpret)
        # named here, where they are the residuals themselves: a checkpoint that keeps them keeps what the backward reads
        out, lse = checkpoint_name(_heads_first(out), CAUSAL_OUT), checkpoint_name(lse, CAUSAL_LSE)
    return out, (q, k, v, k_shared, out, lse)


def _flash_causal_bwd(interpret, res, d_out):
    with step_scope("causal_attn"):
        q, k, v, k_shared, out, lse = res
        b, l, heads, _ = q.shape
        groups, dk = k.shape[2], k.shape[-1]
        delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B, L, H]
        stats = jnp.concatenate([lse, jnp.swapaxes(delta, 1, 2)[:, :, None, :]], axis=2)  # [B, H, 2, L]
        kv = _heads_first(jnp.concatenate([k, v], axis=-1))
        dq, dkv, dk_r = causal_attention_kernels.backward(_heads_first(q), kv, k_shared, _heads_first(d_out), stats,
                                                          interpret=interpret)
        if groups != heads:  # a query head's share of its group's gradient, float32: summed here
            dkv = dkv.reshape(b, groups, heads // groups, l, dkv.shape[-1]).sum(axis=2)
        dk_, dv = jnp.split(_heads_first(dkv), (dk,), axis=-1)
        d_shared = None if dk_r is None else jnp.sum(dk_r, axis=1).astype(k_shared.dtype)
        return _heads_first(dq), dk_.astype(k.dtype), dv.astype(v.dtype), d_shared


_flash_causal.defvjp(_flash_causal_fwd, _flash_causal_bwd)


def causal_attention(q, k, v, k_shared=None):
    """Causal softmax attention, the one entry point of both causal callers: ``q [B, L, H, dk + dr]`` against
    keys ``k [B, L, G, dk]`` (query heads ``g·H/G …`` read key head ``g``) and, where all heads share one,
    a key part ``k_shared [B, L, dr]``; values ``v [B, L, G, dv]`` of a width of their own → ``[B, L, H,
    dv]``. Scores ``(q[:dk]·k + q[dk:]·k_shared) / sqrt(dk + dr)``, causal softmax in float32.

    The kernel pair of `ops/causal_attention.py` (``dtpu_causal_attn_fwd``, ``dtpu_causal_attn_bwd``: the
    scores in VMEM, the causal half only) where its `fits` admits the call for the devices of the mesh in use
    (inside the trainer's `shard_map`'d steps; the described chips of a compile-only test count as what they
    describe), else `xla_causal_core`. Traced outside any mesh it is XLA's, uncounted:
    ``model.init``, shape inference, a test's plain call. The output is named `CAUSAL_OUT` on either route,
    and on the kernels' the rows' log-sum-exp `CAUSAL_LSE`, for a layer checkpoint to keep. Either route
    stands under the step scope ``dtpu.causal_attn``, forward, recomputed and backward."""
    with step_scope("causal_attn"):
        mesh = jax.sharding.get_abstract_mesh()
        fused = False
        if not mesh.empty:
            _, l, heads, width = q.shape
            dr = 0 if k_shared is None else k_shared.shape[-1]
            fused = causal_attention_kernels.fits(mesh.abstract_device.device_kind, l, heads, k.shape[2], width - dr, dr,
                                                  v.shape[-1], np.dtype(q.dtype).itemsize)
            jax.monitoring.record_event(CAUSAL_FUSED_EVENT if fused else CAUSAL_XLA_EVENT)
        if fused:
            return _flash_causal(q, k, v, k_shared, pallas_interpret())
        return checkpoint_name(xla_causal_core(q, k, v, k_shared), CAUSAL_OUT)


def latent_causal_attention(q, k_own, k_shared, v):
    """The causal core of multi-head latent attention in its expanded (training) form: ``q [B, L, H, dk + dr]``
    against keys that are per head in their first ``dk`` dimensions (``k_own [B, L, H, dk]``, expanded from the
    latent) and one head given to all ``H`` in their last ``dr`` (``k_shared [B, L, dr]``, the rotary part), and
    values of a width of their own (``v [B, L, H, dv]``) → ``[B, L, H·dv]``: `causal_attention`, whose kernels
    read the shared part once for every head, and whose XLA blocks copy it to each. The absorbed form (scores
    against the latent itself) is a decoder's and is not here."""
    out = causal_attention(q, k_own, v, k_shared)
    return out.reshape(*out.shape[:2], -1)


def partial_rotary(x, rotary_dim: int, theta: float, first_position: int = 0):
    """Rotary position embedding on the first ``rotary_dim`` of each head of ``x [B, L, H, hd]``, the
    rest untouched. The rotated dimensions pair as ``(i, i + rotary_dim/2)`` ("rotate half"), pair
    ``i`` turning by ``position · theta^(-2i/rotary_dim)``; positions count from ``first_position``.
    Angles, sines and cosines in float32; returns ``x.dtype``."""
    length, half = x.shape[1], rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (first_position + jnp.arange(length, dtype=jnp.float32))[:, None] * inv_freq  # [L, half]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = jnp.split(x.astype(jnp.float32), (half, rotary_dim), axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1).astype(x.dtype)


def self_attention(qkv, num_heads: int, *, kv_heads: int | None = None, causal: bool = False):
    """Multi-head self-attention over a packed projection, two kinds behind
    one entry point.

    Bias-free bidirectional (the default; ``qkv [B, L, 3·D]`` → ``[B, L, D]``):
    `fused_self_attention` or `xla_self_attention`, chosen by
    `self_attention_fuses` from the devices of the mesh in use (inside the
    trainer's `shard_map`'d steps; the described chips of a compile-only
    test count as what they describe) and the shapes. Traced outside any
    mesh it is the einsums, uncounted: ``model.init``, shape inference, and
    programs partitioned by named shardings alone (`serve/engine.py`), where
    a Mosaic call could not be partitioned.

    Causal, grouped-query (``causal=True``, ``kv_heads`` key/value heads;
    ``qkv [B, L, (H + 2·G)·hd]``, one width for queries, keys and values):
    `causal_attention`, whose kernel pair takes it on TPUs at shapes it tiles
    and XLA's blocks elsewhere. Latent attention's heads (keys wider than
    values, a key part that all heads share) are not packed and reach the same
    entry through `latent_causal_attention`."""
    if causal:
        out = causal_attention(*_unpacked(qkv, num_heads, num_heads if kv_heads is None else kv_heads))
        return out.reshape(*out.shape[:2], -1)
    if kv_heads not in (None, num_heads):
        raise ValueError("grouped-query attention is implemented for causal=True only")
    mesh = jax.sharding.get_abstract_mesh()
    device = None if mesh.empty else mesh.abstract_device
    if device is None:
        return xla_self_attention(qkv, num_heads)
    _, l, d3 = qkv.shape
    fused = self_attention_fuses(
        device.device_kind, l, num_heads, d3 // 3 // num_heads, np.dtype(qkv.dtype).itemsize
    )
    jax.monitoring.record_event(FUSED_CALLS_EVENT if fused else XLA_CALLS_EVENT)
    if fused:
        return fused_self_attention(qkv, num_heads, pallas_interpret())
    return xla_self_attention(qkv, num_heads)
