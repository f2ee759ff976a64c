"""Causal softmax attention in flash form: two Mosaic kernels whose scores never leave VMEM.

`ops.attention.causal_attention` takes them for a step traced for TPUs at shapes they tile
(`fits`); elsewhere it takes XLA's blocks (`ops.attention.xla_causal_core`). Per query head ``h``
of ``H``, in the heads-first layout the kernels read:

- queries ``q [B, H, L, dk + dr]``, whose last ``dr`` meet a key part that every head shares
  (latent attention's rotary key);
- a key head's keys and values side by side, ``kv [B, G, L, dk + dv]``, head ``h`` reading group
  ``h // (H / G)`` through the index map (grouped-query attention, nothing copied);
- the shared key ``k_r [B, L, dr]``, whose index map ignores the head, so it is read and not
  copied to every head.

Scores of head ``h`` are ``(q[:dk]·kᵀ + q[dk:]·k_rᵀ) · (dk + dr)^-½``, two products into one
float32 tile. A grid step takes one tile of `block_for` query rows against as many key rows, and
only the tiles on or below the diagonal exist: the grid's last axis counts them, and the index
maps and the kernel find a tile's blocks by comparing that count with the runs' static first
tiles, so nothing above the diagonal is fetched or stepped over. The mask is applied on the
diagonal tiles alone. A call has at most five operands: the harness's HLO reader
(`benchmark/hlo.kernel_calls`) reads no operand list that XLA's printer marks ``/*index=5*/``.

- ``dtpu_causal_attn_fwd``: the tiles of a query block in key order, an online softmax (row max,
  row sum and the float32 accumulator in VMEM scratch); at the diagonal tile, the block's last,
  the output in the compute dtype and the rows' log-sum-exp ``[B, H, 1, L]`` float32.
- ``dtpu_causal_attn_bwd``: FlashAttention-2's backward in one kernel, the tiles of a key block
  in query order: the weights again from q, k and the log-sum-exp, ``dP = dO·vᵀ``,
  ``dS = P∘(dP − δ)`` with ``δ = rowsum(dO∘O)`` (log-sum-exp and δ one operand, ``[B, H, 2, L]``),
  then dV and dK summed over the key block's run in VMEM, and dQ into a float32 scratch of the
  whole row, which a query block leaves complete at its diagonal tile (the first of key block
  ``i``'s run is the last that touches query block ``i``). Transposed scores ``[keys, queries]``,
  so that the log-sum-exp and δ stand as rows. dK and dV are written a query head each, side by
  side, summed over a group by the caller where groups hold several heads, in float32 then; the
  shared key's gradient a head each in float32, summed by the caller.

Precision is the configuration's: every product's operands in the compute dtype with float32
accumulation; scores, softmax statistics, ``dP − δ`` and dS in float32; the weights and dS cast
to the compute dtype as operands of a product and nowhere else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_LANES = 128
#: query (and key) rows a tile: the largest of these that divides the length
BLOCKS = (1024, 512, 256, 128)
#: handed to Mosaic in place of its 16 MiB default (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 64 * 2**20
#: the widest shared key part the kernels take (one lane group)
WIDEST_SHARED = _LANES
FWD_NAME = "dtpu_causal_attn_fwd"
BWD_NAME = "dtpu_causal_attn_bwd"

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                               vmem_limit_bytes=VMEM_LIMIT_BYTES)


def block_for(length: int) -> int | None:
    """Query rows a tile for a length, or None where no tile divides it."""
    return next((b for b in BLOCKS if length % b == 0), None)


def _lanes(width: int) -> int:
    return pl.cdiv(width, _LANES) * _LANES


def bwd_vmem_bytes(length: int, block: int, dk: int, dr: int, dv: int, itemsize: int) -> int:
    """VMEM of one grid step of the backward kernel (the larger of the pair): the row's float32 dQ
    scratch, the tiles of every operand and result double-buffered, the float32 dK/dV
    accumulators and a tile's float32 ``[block, block]`` values (scores, weights, dP, dS and
    their casts: priced as six), every width padded to whole lane groups."""
    widths = _lanes(dk) + (_lanes(dr) if dr else 0)
    tiles = block * (2 * widths + 2 * _lanes(dv)) * itemsize  # q, kv (+ k_r), dO
    results = block * (2 * widths + _lanes(dv)) * 4  # dQ, dK, dV: float32 at the most
    return length * widths * 4 + 2 * (tiles + results) + block * (widths + _lanes(dv)) * 4 + 6 * block * block * 4


def fits(device_kind: str, length: int, heads: int, kv_heads: int, dk: int, dr: int, dv: int, itemsize: int) -> bool:
    """Whether the pair takes a call: traced for TPUs, the query heads whole groups of the key
    heads, a length some tile of `BLOCKS` divides, the own key and value widths whole lane groups,
    a shared key part of one lane group at the most, and the backward's step inside
    `VMEM_LIMIT_BYTES`."""
    block = block_for(length)
    return (
        device_kind.upper().startswith("TPU")
        and heads % kv_heads == 0
        and block is not None
        and dk % _LANES == 0 and dv % _LANES == 0 and 0 <= dr <= WIDEST_SHARED
        and bwd_vmem_bytes(length, block, dk, dr, dv, itemsize) <= VMEM_LIMIT_BYTES
    )


def _starts(blocks: int, by_key: bool) -> list[int]:
    """The first tile of each run of the tiles on and below the diagonal: by query block, its keys in order
    (the diagonal last), for the forward; by key block, its queries in order (the diagonal first), for the
    backward."""
    lengths = [blocks - run for run in range(blocks)] if by_key else [run + 1 for run in range(blocks)]
    return [sum(lengths[:run]) for run in range(blocks)]


def _tile(t, blocks: int, by_key: bool):
    """``(query block, key block)`` of the grid's ``t``-th tile, from comparisons with the runs' static first
    tiles: scalar work in the index maps and the kernel, and no table to read."""
    run, first = jnp.int32(0), jnp.int32(0)
    for start in _starts(blocks, by_key)[1:]:
        reached = t >= start
        run, first = run + reached.astype(jnp.int32), jnp.where(reached, start, first)
    offset = t - first
    return (run + offset, run) if by_key else (run, offset)


# a tile's products, float32 accumulation (the bias-free pair of ops/attention.py uses them too)
def nt(a, b):  # a·bᵀ
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def nn(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())), preferred_element_type=_F32)


def tn(a, b):  # aᵀ·b
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=_F32)


def _on_or_below(shape, transposed: bool):
    """A diagonal tile's mask: key position <= query position (rows are keys where ``transposed``)."""
    rows, cols = (lax.broadcasted_iota(jnp.int32, shape, d) for d in (0, 1))
    return rows <= cols if transposed else cols <= rows


def _fwd_kernel(q_ref, kv_ref, *refs, dk: int, scale: float, blocks: int):
    if len(refs) == 6:
        kr_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc = refs
    else:
        (o_ref, lse_ref, m_sc, l_sc, acc_sc), kr_ref = refs, None
    qi, ki = _tile(pl.program_id(2), blocks, by_key=False)

    @pl.when(ki == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, _F32)
        l_sc[...] = jnp.zeros(l_sc.shape, _F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, _F32)

    def step(diagonal: bool):
        s = nt(q_ref[:, :dk], kv_ref[:, :dk])
        if kr_ref is not None:
            s = s + nt(q_ref[:, dk:], kr_ref[...])
        s = s * scale
        if diagonal:
            s = jnp.where(_on_or_below(s.shape, False), s, -jnp.inf)  # every row keeps its own key
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # the block's first tile: exp(-inf) = 0
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + nn(p.astype(kv_ref.dtype), kv_ref[:, dk:])
        m_sc[...] = m_new

    pl.when(ki < qi)(lambda: step(False))

    @pl.when(ki == qi)
    def _():
        step(True)
        o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l_sc[...])  # [block, 1]
        lse_ref[...] = jnp.transpose(jnp.broadcast_to(lse, (lse.shape[0], _LANES)))[:1]  # as a row


def _specs(block: int, blocks: int, per: int, by_key: bool):
    """Block specs of a tile's rows of the query heads' arrays, of the key heads' arrays (head ``h`` reads
    group ``h // per``), of the shared key, of a query head's key rows (the backward's results) and of the
    rows' float32 statistics ``[B, H, ·, L]``."""
    tile = lambda t: _tile(t, blocks, by_key)
    queries = lambda width: pl.BlockSpec((None, None, block, width), lambda b, h, t: (b, h, tile(t)[0], 0))
    keys = lambda width: pl.BlockSpec((None, None, block, width), lambda b, h, t: (b, h // per, tile(t)[1], 0))
    shared = lambda width: pl.BlockSpec((None, block, width), lambda b, h, t: (b, tile(t)[1], 0))
    key_rows = lambda width: pl.BlockSpec((None, None, block, width), lambda b, h, t: (b, h, tile(t)[1], 0))
    stats = lambda rows: pl.BlockSpec((None, None, rows, block), lambda b, h, t: (b, h, 0, tile(t)[0]))
    return queries, keys, shared, key_rows, stats


def forward(q, kv, k_r=None, *, interpret: bool = False):
    """``dtpu_causal_attn_fwd``: ``q [B, H, L, dk + dr]``, keys and values of a group side by side
    ``kv [B, G, L, dk + dv]``, the shared key ``k_r [B, L, dr]`` or None -> the output ``[B, H, L, dv]`` in
    q's dtype and the rows' log-sum-exp ``[B, H, 1, L]`` float32."""
    b, heads, length, dq = q.shape
    groups = kv.shape[1]
    dr = 0 if k_r is None else k_r.shape[-1]
    dk = dq - dr
    dv = kv.shape[-1] - dk
    block = block_for(length)
    blocks = length // block
    queries, keys, shared, _, stats = _specs(block, blocks, heads // groups, by_key=False)
    kernel = functools.partial(_fwd_kernel, dk=dk, scale=dq ** -0.5, blocks=blocks)
    return pl.pallas_call(
        kernel,
        grid=(b, heads, blocks * (blocks + 1) // 2),
        in_specs=[queries(dq), keys(dk + dv)] + ([] if k_r is None else [shared(dr)]),
        out_specs=[queries(dv), stats(1)],
        out_shape=[jax.ShapeDtypeStruct((b, heads, length, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, length), _F32)],
        scratch_shapes=[pltpu.VMEM((block, 1), _F32), pltpu.VMEM((block, 1), _F32), pltpu.VMEM((block, dv), _F32)],
        compiler_params=_PARAMS,
        name=FWD_NAME,
        interpret=interpret,
    )(q, kv, *(() if k_r is None else (k_r,)))


def _bwd_kernel(q_ref, kv_ref, *refs, dk: int, scale: float, block: int, blocks: int):
    if len(refs) == 10:
        kr_ref, do_ref, stats_ref, dq_ref, dkv_ref, dkr_ref, dq_sc, dk_sc, dv_sc, dkr_sc = refs
        accumulators = (dk_sc, dv_sc, dkr_sc)
    else:
        do_ref, stats_ref, dq_ref, dkv_ref, dq_sc, dk_sc, dv_sc = refs
        kr_ref = dkr_ref = None
        accumulators = (dk_sc, dv_sc)
    qi, ki = _tile(pl.program_id(2), blocks, by_key=True)
    rows = pl.ds(pl.multiple_of(qi * block, block), block)  # the query block's rows of the dQ scratch

    @pl.when(qi == ki)  # a key block's run begins at its diagonal
    def _():
        for acc in accumulators:
            acc[...] = jnp.zeros(acc.shape, _F32)

    @pl.when(ki == 0)  # a query block first met
    def _():
        dq_sc[rows] = jnp.zeros((block, dq_sc.shape[1]), _F32)

    def step(diagonal: bool):
        q, k, do = q_ref[:, :dk], kv_ref[:, :dk], do_ref[...]
        s = nt(k, q)  # [keys, queries]
        if kr_ref is not None:
            s = s + nt(kr_ref[...], q_ref[:, dk:])
        s = s * scale
        if diagonal:
            s = jnp.where(_on_or_below(s.shape, True), s, -jnp.inf)
        p = jnp.exp(s - stats_ref[0:1, :])  # the forward's weights from its log-sum-exp, float32
        dp = nt(kv_ref[:, dk:], do)
        ds = (p * (dp - stats_ref[1:2, :]) * scale).astype(q.dtype)  # δ in the second row
        dv_sc[...] += nn(p.astype(do.dtype), do)
        dk_sc[...] += nn(ds, q)
        dq_sc[rows, :dk] += tn(ds, k)
        if kr_ref is not None:
            dkr_sc[...] += nn(ds, q_ref[:, dk:])
            dq_sc[rows, dk:] += tn(ds, kr_ref[...])

    pl.when(qi > ki)(lambda: step(False))

    @pl.when(qi == ki)  # the query block's last tile: its dQ is whole
    def _():
        step(True)
        dq_ref[...] = dq_sc[rows].astype(dq_ref.dtype)

    @pl.when(qi == blocks - 1)  # the key block's run ends at the last query block
    def _():
        dkv_ref[:, :dk] = dk_sc[...].astype(dkv_ref.dtype)
        dkv_ref[:, dk:] = dv_sc[...].astype(dkv_ref.dtype)
        if dkr_ref is not None:
            dkr_ref[...] = dkr_sc[...]


def backward(q, kv, k_r, d_out, stats, *, interpret: bool = False):
    """``dtpu_causal_attn_bwd``: ``(dq, dkv, dk_r)`` from the forward's operands, the output's gradient
    ``[B, H, L, dv]`` and the rows' statistics ``[B, H, 2, L]`` float32 (the log-sum-exp, then
    ``δ = rowsum(dO∘O)``). ``dkv [B, H, L, dk + dv]`` is a query head's share (float32 where a group holds
    several heads); ``dk_r [B, H, L, dr]`` float32, a head's share, or None without a shared key."""
    b, heads, length, dq = q.shape
    groups = kv.shape[1]
    per = heads // groups
    dr = 0 if k_r is None else k_r.shape[-1]
    dk = dq - dr
    dv = kv.shape[-1] - dk
    block = block_for(length)
    blocks = length // block
    queries, keys, shared, key_rows, stats_spec = _specs(block, blocks, per, by_key=True)
    # every result is written a key block's run at a time, dQ too: query block i's at key block i's diagonal
    out_specs = [key_rows(dq), key_rows(dk + dv)]
    out_shape = [jax.ShapeDtypeStruct((b, heads, length, dq), q.dtype),
                 jax.ShapeDtypeStruct((b, heads, length, dk + dv), q.dtype if per == 1 else _F32)]
    scratch = [pltpu.VMEM((length, dq), _F32), pltpu.VMEM((block, dk), _F32), pltpu.VMEM((block, dv), _F32)]
    in_specs = [queries(dq), keys(dk + dv)]
    if k_r is not None:
        in_specs.append(shared(dr))
        out_specs.append(key_rows(dr))
        out_shape.append(jax.ShapeDtypeStruct((b, heads, length, dr), _F32))
        scratch.append(pltpu.VMEM((block, dr), _F32))
    kernel = functools.partial(_bwd_kernel, dk=dk, scale=dq ** -0.5, block=block, blocks=blocks)
    grads = pl.pallas_call(
        kernel,
        grid=(b, heads, blocks * (blocks + 1) // 2),
        in_specs=in_specs + [queries(dv), stats_spec(2)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_PARAMS,
        name=BWD_NAME,
        interpret=interpret,
    )(q, kv, *(() if k_r is None else (k_r,)), d_out, stats)
    return tuple(grads) if k_r is not None else (*grads, None)
