"""Fused MoE dispatch/combine — Pallas TPU kernels for `switch_moe`.

The einsum formulation in `parallel/moe.py` materializes an ``[n, E, C]``
float32 dispatch mask in HBM and round-trips it through two one-hot
contractions per step (dispatch before the all_to_all, the transpose after).
At production shards (n = tens of thousands of tokens, E·C in the thousands)
that mask is the dominant HBM traffic of the MoE layer — and it is pure
routing metadata, recomputable from ``[n]``-sized integers.

These kernels keep the whole routing pipeline VMEM-resident per token tile:

- **dispatch**: gate logits → softmax → top-1 → running capacity slots →
  the ``[T, E·C]`` one-hot mask built in VMEM → one MXU contraction
  accumulating the packed ``[E, C, D]`` send buffer. The mask never touches
  HBM; what leaves the kernel besides ``send`` is ``[n]``-sized metadata
  (chosen expert, capacity slot, combine weight) plus the ``[2, E]`` sums
  the load-balancing aux loss needs.
- **combine**: the transpose — rebuild the mask tile from the metadata and
  contract it with the returned ``[E, C, D]`` buffer back to token order.

Capacity slots are counted in **int32** carried across token tiles in SMEM
scratch (same rationale as `moe.token_slot_positions`: a float32 cumsum
saturates at 2^24). Both kernels are differentiable via `jax.custom_vjp`
whose backward *recomputes* the einsum formulation with XLA and transposes
through it (flash-attention-style recompute — the mask is cheaper to rebuild
than to save), so gradients are exactly the einsum path's gradients.

Oracle equality (fwd + grad, including the drop-at-capacity boundary) is
pinned against the einsum formulation in tests/test_moe_kernel.py via the
interpret-mode pattern every kernel in this repo uses. Opt-in from
`switch_moe(..., fused=True)`: interpret-verified only; Mosaic refuses both
kernels at every shape tried (tests/test_chip_compile.py, ROADMAP.md Design 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distribuuuu_tpu.ops.vmem_guard import VmemBudgetGuard


def _float0_like(a):
    """The cotangent custom_vjp expects for an integer-typed argument."""
    return np.zeros(a.shape, jax.dtypes.float0)


# VMEM-budget guard (the ops/attention.py convention): both kernels keep the
# whole [E, C, D] packed buffer VMEM-resident, so past the per-core budget
# the Mosaic compile would fail with an opaque allocation error. Estimate up
# front and fall back to the einsum formulation — which is numerically
# IDENTICAL by construction (it is the kernels' own backward) — with one
# warning per shape.
_VMEM_GUARD = VmemBudgetGuard("DTPU_MOE_VMEM_BUDGET_MB")


def _tile_vmem_bytes(t: int, e: int, c: int, d: int) -> int:
    """Per-grid-step estimate: the [E, C, D] f32 buffer held across steps,
    the [T, E·C] f32 mask, double-buffered [T, D] tiles, and the gate/small
    blocks. Same shape for dispatch and combine (send vs back, pack vs
    unpack)."""
    buffer_ecd = e * c * d * 4
    mask = t * e * c * 4
    tiles = 2 * 2 * t * d * 4  # x/out tile, double-buffered
    small = d * e * 4 + 3 * t * 4 + 2 * e * 4
    return buffer_ecd + mask + tiles + small


def _within_vmem_budget(kind: str, t: int, e: int, c: int, d: int) -> bool:
    return _VMEM_GUARD.within(
        kind,
        (kind, t, e, c, d),
        _tile_vmem_bytes(t, e, c, d),
        f"falling back to the (numerically identical) einsum formulation at "
        f"E={e}, C={c}, D={d}; shrink capacity/model dim per shard",
    )


# ---------------------------------------------------------------------------
# Oracle: the einsum formulation, producing EXACTLY the fused outputs.
# Shared by the custom-VJP backward (XLA recompute) and the equality tests.
# ---------------------------------------------------------------------------

def oracle_dispatch(x, gate_kernel, capacity: int):
    """Einsum-formulation dispatch: ``(send, top, pos, w, fp_sum)``.

    Mirrors `switch_moe`'s routing math term for term (f32 softmax gate,
    int32 slot counting, drop past capacity) so the fused kernel has a
    bit-for-bit-comparable reference. ``w = top_p · keep`` is the combine
    weight; ``fp_sum[0] = Σ onehot`` and ``fp_sum[1] = Σ probs`` are the
    (pre-drop) sums the switch aux loss is built from.
    """
    n, d = x.shape
    e = gate_kernel.shape[-1]
    x32 = x.astype(jnp.float32)
    probs = jax.nn.softmax(
        jax.lax.dot_general(
            x32,
            gate_kernel.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ),
        axis=-1,
    )
    top = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    # gather (not jnp.max): the forward values are identical, but under TIED
    # probabilities max's gradient splits across the ties while the einsum
    # path's take_along_axis sends it to the argmax alone — and this oracle
    # IS the fused path's backward, so it must transpose like the einsum path
    top_p = jnp.take_along_axis(probs, top[:, None], axis=-1)[:, 0]
    onehot_e = jax.nn.one_hot(top, e, dtype=jnp.float32)
    oh = onehot_e.astype(jnp.int32)
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)
    keep = pos < capacity
    pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    onehot_c = jax.nn.one_hot(pos_c, capacity, dtype=jnp.float32)
    dispatch = (
        onehot_e[:, :, None]
        * onehot_c[:, None, :]
        * keep[:, None, None].astype(jnp.float32)
    )
    send = jnp.einsum(
        "nec,nd->ecd", dispatch, x32, preferred_element_type=jnp.float32
    )
    w = top_p * keep.astype(jnp.float32)
    fp_sum = jnp.stack([jnp.sum(onehot_e, axis=0), jnp.sum(probs, axis=0)])
    return send, top, pos_c, w, fp_sum


def oracle_combine(back, top, pos, w):
    """Einsum-formulation combine: ``out[t] = w_t · back[top_t, pos_t]``."""
    e, c, d = back.shape
    mask = (
        jax.nn.one_hot(top, e, dtype=jnp.float32)[:, :, None]
        * jax.nn.one_hot(pos, c, dtype=jnp.float32)[:, None, :]
        * w[:, None, None]
    )
    return jnp.einsum(
        "nec,ecd->nd", mask, back.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


# ---------------------------------------------------------------------------
# Dispatch kernel
# ---------------------------------------------------------------------------

def _dispatch_kernel(
    x_ref, g_ref, send_ref, top_ref, pos_ref, w_ref, fp_ref, counts_ref,
    *, n: int, t: int, e: int, c: int,
):
    """One [T, D] token tile: gate → slots → pack, all VMEM-resident.

    ``send_ref``/``fp_ref`` map the same block every grid step (sequential on
    TPU) and accumulate; ``counts_ref`` carries the per-expert running slot
    count across tiles in SMEM — the int32 cross-tile cumsum.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        send_ref[...] = jnp.zeros_like(send_ref)
        fp_ref[...] = jnp.zeros_like(fp_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    # rows past n (the ragged last tile) read padding: zero them so a stray
    # non-finite bit pattern can't poison the masked contractions (0·NaN=NaN)
    token = i * t + jax.lax.broadcasted_iota(jnp.int32, (t, e), 0)[:, 0]
    valid = token < n  # [T]
    x = jnp.where(valid[:, None], x_ref[...].astype(jnp.float32), 0.0)  # [T, D]
    logits = jax.lax.dot_general(
        x, g_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [T, E]
    m = jnp.max(logits, axis=-1, keepdims=True)
    ex = jnp.exp(logits - m)
    probs = ex / jnp.sum(ex, axis=-1, keepdims=True)
    top = jnp.argmax(probs, axis=-1).astype(jnp.int32)  # [T]
    top_p = jnp.max(probs, axis=-1)  # [T]

    # rows past n must not claim slots or pollute the aux sums: zero their
    # one-hot before anything derived from it
    eidx = jax.lax.broadcasted_iota(jnp.int32, (t, e), 1)
    onehot = jnp.where(
        (eidx == top[:, None]) & valid[:, None], jnp.int32(1), jnp.int32(0)
    )  # [T, E] int32

    # slot = running count of earlier tokens (this tile + the carry) that
    # chose the same expert — int32 end to end (moe.token_slot_positions)
    cum = jnp.cumsum(onehot, axis=0)
    carry = counts_ref[0, :]  # [E] int32
    pos = jnp.sum((cum - 1 + carry[None, :]) * onehot, axis=-1)  # [T]
    counts_ref[0, :] = carry + cum[-1, :]
    routed = jnp.sum(onehot, axis=-1) > 0  # valid rows only
    keep = (pos < c) & routed
    pos_c = jnp.clip(pos, 0, c - 1)
    w = jnp.where(keep, top_p, 0.0)

    cidx = jax.lax.broadcasted_iota(jnp.int32, (t, c), 1)
    onehot_c = (cidx == pos_c[:, None]).astype(jnp.float32)  # [T, C]
    mask = (
        onehot.astype(jnp.float32)[:, :, None]
        * onehot_c[:, None, :]
        * keep.astype(jnp.float32)[:, None, None]
    ).reshape(t, e * c)
    send_ref[...] += jax.lax.dot_general(
        mask, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).reshape(e, c, x.shape[-1])

    top_ref[0, :] = top
    pos_ref[0, :] = pos_c
    w_ref[0, :] = w
    fp_ref[0, :] += jnp.sum(onehot.astype(jnp.float32), axis=0)
    fp_ref[1, :] += jnp.sum(
        jnp.where(valid[:, None], probs, 0.0), axis=0
    )


def _dispatch_impl(x, gate_kernel, capacity, block_n, interpret):
    n, d = x.shape
    e = gate_kernel.shape[-1]
    t = min(block_n, n)
    grid = pl.cdiv(n, t)
    send, top, pos, w, fp_sum = pl.pallas_call(
        functools.partial(_dispatch_kernel, n=n, t=t, e=e, c=capacity),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((d, e), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((e, capacity, d), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, t), lambda i: (0, i)),
            pl.BlockSpec((1, t), lambda i: (0, i)),
            pl.BlockSpec((1, t), lambda i: (0, i)),
            pl.BlockSpec((2, e), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e, capacity, d), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((2, e), jnp.float32),
        ],
        scratch_shapes=[pltpu.SMEM((1, e), jnp.int32)],
        interpret=interpret,
    )(x.astype(jnp.float32), gate_kernel.astype(jnp.float32))
    return send, top[0], pos[0], w[0], fp_sum


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_dispatch(x, gate_kernel, capacity, block_n, interpret):
    return _dispatch_impl(x, gate_kernel, capacity, block_n, interpret)


def _dispatch_fwd(x, gate_kernel, capacity, block_n, interpret):
    return _dispatch_impl(x, gate_kernel, capacity, block_n, interpret), (
        x,
        gate_kernel,
    )


def _dispatch_bwd(capacity, block_n, interpret, res, cts):
    # XLA recompute: transpose through the einsum formulation. top/pos are
    # integer outputs — their float0 cotangents carry nothing.
    x, gate_kernel = res
    d_send, _d_top, _d_pos, d_w, d_fp = cts

    def diff_outputs(x_, g_):
        send, _top, _pos, w, fp = oracle_dispatch(x_, g_, capacity)
        return send, w, fp

    _, pull = jax.vjp(diff_outputs, x, gate_kernel)
    return pull((d_send, d_w, d_fp))


_fused_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def fused_moe_dispatch(
    x, gate_kernel, *, capacity: int, block_n: int = 128, interpret: bool = False
):
    """Gate → capacity slots → packed ``[E, C, D]`` send buffer, fused.

    Returns ``(send, top, pos, w, fp_sum)`` — exactly `oracle_dispatch`'s
    contract. ``x`` is the local ``[n, D]`` token shard (any float dtype;
    routing and packing are f32 like the einsum path), ``gate_kernel`` is
    ``[D, E]``. Differentiable; the backward recomputes with XLA einsums.
    A tile set too large for VMEM (the ``[E, C, D]`` buffer dominates)
    falls back to the identical einsum formulation with a one-time warning
    instead of failing opaquely inside Mosaic.
    """
    n, d = x.shape
    e = gate_kernel.shape[-1]
    if not _within_vmem_budget(
        "fused_moe_dispatch", min(int(block_n), n), e, int(capacity), d
    ):
        return oracle_dispatch(x, gate_kernel, int(capacity))
    return _fused_dispatch(x, gate_kernel, int(capacity), int(block_n), interpret)


# ---------------------------------------------------------------------------
# Combine kernel
# ---------------------------------------------------------------------------

def _combine_kernel(back_ref, top_ref, pos_ref, w_ref, out_ref, *, t: int, e: int, c: int):
    """One [T, D] output tile: rebuild the mask from [T] metadata, contract
    with the full (VMEM-resident) ``[E, C, D]`` return buffer."""
    top = top_ref[0, :]
    pos = pos_ref[0, :]
    w = w_ref[0, :]
    eidx = jax.lax.broadcasted_iota(jnp.int32, (t, e), 1)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (t, c), 1)
    mask = (
        (eidx == top[:, None]).astype(jnp.float32)[:, :, None]
        * (cidx == pos[:, None]).astype(jnp.float32)[:, None, :]
        * w[:, None, None]
    ).reshape(t, e * c)
    back = back_ref[...].astype(jnp.float32).reshape(e * c, back_ref.shape[-1])
    out_ref[...] = jax.lax.dot_general(
        mask, back, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _combine_impl(back, top, pos, w, block_n, interpret):
    e, c, d = back.shape
    n = top.shape[0]
    t = min(block_n, n)
    grid = pl.cdiv(n, t)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, t=t, e=e, c=c),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((e, c, d), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, t), lambda i: (0, i)),
            pl.BlockSpec((1, t), lambda i: (0, i)),
            pl.BlockSpec((1, t), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(back.astype(jnp.float32), top[None], pos[None], w[None])
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_combine(back, top, pos, w, block_n, interpret):
    return _combine_impl(back, top, pos, w, block_n, interpret)


def _combine_fwd(back, top, pos, w, block_n, interpret):
    return _combine_impl(back, top, pos, w, block_n, interpret), (back, top, pos, w)


def _combine_bwd(block_n, interpret, res, g):
    back, top, pos, w = res
    _, pull = jax.vjp(lambda b_, w_: oracle_combine(b_, top, pos, w_), back, w)
    d_back, d_w = pull(g)
    return d_back, _float0_like(top), _float0_like(pos), d_w


_fused_combine.defvjp(_combine_fwd, _combine_bwd)


def fused_moe_combine(
    back, top, pos, w, *, block_n: int = 128, interpret: bool = False
):
    """The transposed un-pack: ``out[t] = w_t · back[top_t, pos_t]``, fused.

    ``back`` is the post-all_to_all ``[E, C, D]`` expert-output buffer;
    ``top``/``pos``/``w`` are the ``[n]`` routing metadata `fused_moe_dispatch`
    returned. Dropped tokens (``w == 0``) combine to exact zeros, matching
    the einsum path's drop semantics. Differentiable in ``back`` and ``w``.
    Over the VMEM budget it falls back to the identical einsum formulation
    (same guard as dispatch, so both sides of the all_to_all flip together).
    """
    e, c, d = back.shape
    if not _within_vmem_budget(
        "fused_moe_combine", min(int(block_n), top.shape[0]), e, c, d
    ):
        return oracle_combine(back, top, pos, w)
    return _fused_combine(back, top, pos, w, int(block_n), interpret)
