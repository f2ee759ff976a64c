"""The short causal convolution and its `silu` (`ops.short_conv`) as two Mosaic kernels, a row's whole length of a
128-lane tile of channels in VMEM.

A grid step takes ``[L, 128]`` of one row: every tap's window of the time axis lies inside the block, so no
halo crosses a block's edge and nothing but the operands and results crosses HBM. Inside, a loop walks the
length in chunks of `ROWS`; a tap's window is the chunk rolled along the sublanes, its first rows taken from
the `HALO` rows before it (zero before the start).

- ``dtpu_short_conv_fwd``: ``x``, ``w [K, C]`` (and ``b [1, C]``) in, ``y = silu(b + Σ_j w[j] · x[t − (K−1) + j])``
  out in the caller's dtype; sums in float32.
- ``dtpu_short_conv_bwd``: ``x``, ``dy``, ``w`` (and ``b``) in. A first walk computes the pre-activation again,
  ``g = dy · silu'(pre)`` into a float32 scratch of the block's length (and `HALO` rows of zeros past the end),
  and sums ``g · x[t − (K−1) + j]`` and ``g`` over the rows in float32; a second walk reads ``g``'s windows the
  other way, ``dx[t] = Σ_j w[j] · g[t + (K−1) − j]``, cast once to ``x``'s dtype. Out: ``dx`` and a row's
  ``[K (+1), C]`` sums, which the caller adds over the rows.

`fits` says from the device kind and the shapes whether the pair takes a call; `ops.short_conv` asks it and keeps
XLA's form otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
#: channels a grid step: one lane group
LANES = 128
#: rows a step of the loop inside a block
ROWS = 256
#: rows read before (forward) or after (backward) a chunk for the taps' windows: a whole sublane tile of every dtype
HALO = 16
#: handed to Mosaic in place of its 16 MiB default (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 40 * 2**20
#: what a step's blocks may take together: operands and results double-buffered, and the backward's scratch
BLOCK_VMEM_BYTES = 32 * 2**20

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _block_bytes(length: int, x_itemsize: int, out_itemsize: int) -> int:
    """VMEM of the backward's grid step, the larger of the two: ``x``, ``dy`` and ``dx`` double-buffered, ``g``."""
    return LANES * (2 * length * (2 * x_itemsize + out_itemsize) + 4 * (length + HALO))


def fits(device_kind: str, length: int, channels: int, taps: int, x_itemsize: int, out_itemsize: int) -> bool:
    """Whether the pair takes ``x [B, length, channels]``: traced for TPUs, whole 128-lane groups of channels, a
    length of whole `ROWS` chunks, at most `HALO` + 1 taps, and a step's blocks inside `BLOCK_VMEM_BYTES`."""
    return (device_kind.upper().startswith("TPU") and channels % LANES == 0 and length % ROWS == 0
            and 1 <= taps <= HALO + 1 and _block_bytes(length, x_itemsize, out_itemsize) <= BLOCK_VMEM_BYTES)


def _later(cur, before, s: int):
    """``out[t] = chunk[t − s]`` over a chunk ``cur [ROWS, LANES]``, the first ``s`` rows from the end of
    ``before [HALO, LANES]``, the rows just before the chunk."""
    if s == 0:
        return cur
    rolled = pltpu.roll(cur, s, 0)
    head = jnp.where(lax.broadcasted_iota(jnp.int32, (HALO, 1), 0) < s, pltpu.roll(before, s, 0), rolled[:HALO])
    return jnp.concatenate([head, rolled[HALO:]], axis=0)


def _earlier(cur, after, s: int):
    """``out[t] = chunk[t + s]`` over a chunk ``cur [ROWS, LANES]``, the last ``s`` rows from the start of
    ``after [HALO, LANES]``, the rows just after the chunk."""
    if s == 0:
        return cur
    rows = cur.shape[0]
    rolled = pltpu.roll(cur, rows - s, 0)
    tail = jnp.where(lax.broadcasted_iota(jnp.int32, (HALO, 1), 0) >= HALO - s, pltpu.roll(after, HALO - s, 0),
                     rolled[rows - HALO:])
    return jnp.concatenate([rolled[:rows - HALO], tail], axis=0)


def _windows(x_ref, i):
    """Chunk ``i`` of the block's row in float32 and its taps' windows ``x[t − s]``, ``s = K−1 … 0``."""
    start = pl.multiple_of(i * ROWS, ROWS)
    cur = x_ref[0, pl.ds(start, ROWS), :].astype(_F32)
    before = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(start - HALO, 0), HALO), HALO), :].astype(_F32)
    return start, cur, jnp.where(i > 0, before, 0.0)


def _pre(w, bias, cur, before):
    """The pre-activation of a chunk and its taps' windows (tap ``j`` reads ``x[t − (K−1) + j]``)."""
    taps = w.shape[0]
    windows = [_later(cur, before, taps - 1 - j) for j in range(taps)]
    pre = sum(w[j:j + 1] * windows[j] for j in range(taps))
    return (pre if bias is None else pre + bias), windows


def _fwd_kernel(x_ref, w_ref, *refs):
    *b_ref, y_ref = refs
    w = w_ref[...].astype(_F32)
    bias = b_ref[0][...].astype(_F32) if b_ref else None

    def chunk(i, carry):
        start, cur, before = _windows(x_ref, i)
        pre, _ = _pre(w, bias, cur, before)
        y_ref[0, pl.ds(start, ROWS), :] = jax.nn.silu(pre).astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, x_ref.shape[1] // ROWS, chunk, 0)


def _bwd_kernel(x_ref, dy_ref, w_ref, *refs):
    *b_ref, dx_ref, sums_ref, g_ref = refs
    w = w_ref[...].astype(_F32)
    taps = w.shape[0]
    bias = b_ref[0][...].astype(_F32) if b_ref else None
    length = x_ref.shape[1]
    g_ref[pl.ds(length, HALO), :] = jnp.zeros((HALO, LANES), _F32)

    def sums_of(t):  # [ROWS, LANES] -> [8, LANES]: whole sublane tiles added, the last 8 rows summed at the end
        return jnp.sum(t.reshape(ROWS // 8, 8, LANES), axis=0)

    def first(i, acc):
        start, cur, before = _windows(x_ref, i)
        pre, windows = _pre(w, bias, cur, before)
        sig = jax.nn.sigmoid(pre)
        g = dy_ref[0, pl.ds(start, ROWS), :].astype(_F32) * sig * (1.0 + pre * (1.0 - sig))
        g_ref[pl.ds(start, ROWS), :] = g
        return tuple(a + sums_of(g * t) for a, t in zip(acc, windows + [1.0] * len(b_ref)))

    acc = lax.fori_loop(0, length // ROWS, first, tuple(jnp.zeros((8, LANES), _F32) for _ in range(taps + len(b_ref))))
    sums_ref[0] = jnp.concatenate([jnp.sum(a, axis=0, keepdims=True) for a in acc], axis=0)

    def second(i, carry):
        start = pl.multiple_of(i * ROWS, ROWS)
        cur, after = g_ref[pl.ds(start, ROWS), :], g_ref[pl.ds(start + ROWS, HALO), :]
        dx = sum(w[j:j + 1] * _earlier(cur, after, taps - 1 - j) for j in range(taps))
        dx_ref[0, pl.ds(start, ROWS), :] = dx.astype(dx_ref.dtype)
        return carry

    lax.fori_loop(0, length // ROWS, second, 0)


def _row_block(length: int):
    return pl.BlockSpec((1, length, LANES), lambda r, c: (r, 0, c))


def _weights_block(rows: int):
    return pl.BlockSpec((rows, LANES), lambda r, c: (0, c))


def forward(x, w, b, out_dtype, *, interpret: bool = False):
    """``silu(b + Σ_j w[j] · x[t − (K−1) + j])`` for ``x [B, L, C]``, ``w [K, C]``, ``b [C]`` or None:
    ``dtpu_short_conv_fwd``."""
    rows, length, channels = x.shape
    operands = (x, w) if b is None else (x, w, b.reshape(1, channels))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(rows, channels // LANES),
        in_specs=[_row_block(length), _weights_block(w.shape[0])] + [_weights_block(1)] * (b is not None),
        out_specs=_row_block(length),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        compiler_params=_PARAMS,
        name="dtpu_short_conv_fwd",
        interpret=interpret,
    )(*operands)


def backward(x, w, b, dy, *, interpret: bool = False):
    """``dx`` in ``x``'s dtype and a row's float32 sums ``[B, K (+1), C]``: ``Σ_t g[t] · x[t − (K−1) + j]`` for
    each tap, then ``Σ_t g[t]`` where there is a bias: ``dtpu_short_conv_bwd``."""
    rows, length, channels = x.shape
    sums = w.shape[0] + (b is not None)
    operands = (x, dy, w) if b is None else (x, dy, w, b.reshape(1, channels))
    return pl.pallas_call(
        _bwd_kernel,
        grid=(rows, channels // LANES),
        in_specs=[_row_block(length), _row_block(length), _weights_block(w.shape[0])]
        + [_weights_block(1)] * (b is not None),
        out_specs=(_row_block(length), pl.BlockSpec((1, sums, LANES), lambda r, c: (r, 0, c))),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((rows, sums, channels), _F32)),
        scratch_shapes=[pltpu.VMEM((length + HALO, LANES), _F32)],
        compiler_params=_PARAMS,
        name="dtpu_short_conv_bwd",
        interpret=interpret,
    )(*operands)
