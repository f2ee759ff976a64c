"""The inverse of a chunk's unit lower-triangular matrix with the tile resident in VMEM.

`ops.gdn.unit_lower_inverse` inverts ``I + a`` for every chunk and head of a
row at once: ``N`` tiles of ``Q x Q`` float32 (4096 of 64 x 64 at the second
token cell). As XLA's batched products each of the ten products of the
squarings reads two ``[N, Q, Q]`` tensors from HBM and writes one. Two Mosaic
kernels keep a tile's whole chain on the chip, `TILES` tiles a grid step:

- ``dtpu_gdn_inverse``: ``a [N, Q, Q]`` in, ``T = (I + a)⁻¹`` out. The same
  squarings of the nilpotent ``n = −a`` as the XLA body,
  ``(I + n)(I + n²)(I + n⁴) …``, the same products on the same operands, laid
  out for the 128 x 128 matrix unit: where ``Q`` divides 128, ``128 / Q`` tiles
  stand side by side in the lanes, ``[x₀ | x₁]``, and are multiplied from the
  right by the block-diagonal of their own right operands, so a pass runs at
  the unit's full depth and width and each tile's result is its own product
  plus exact zeros; and a squaring and the product that takes the factor
  before it, which share their right operand (``n^s · n^s`` and
  ``T_{s/2} · n^s``), go through as one product of ``2Q`` rows.
- ``dtpu_gdn_inverse_bwd``: ``(T, dT)`` in, ``da = −Tᵀ dT Tᵀ`` out, both products
  and the transpose in VMEM.

Every product is float32's own: float32 operands at `lax.Precision.HIGHEST`,
accumulated in float32, as the configuration's ``precision`` states for the
triangular inverse. `inverse_fits` says from the device kind and the shape
whether the pair takes a call; `ops.gdn` asks it and keeps XLA's products
otherwise. A tile count that is no multiple of `TILES` leaves the last grid
step spare tiles, which are read as zeros (side by side with a live tile,
whatever the buffer held would reach it as ``0 · x``) and never written back.
For the same reason a tile that holds a non-finite value takes its lane
neighbours with it, where XLA's products would keep them apart; a step that
holds one is the non-finite guard's either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_LANES = 128
#: tiles a grid step. A float32 tile of under 128 columns stands in VMEM padded to 128 lanes, operands and result
#: are double-buffered and a chain keeps a dozen values of a block's size: 9 MiB at Q = 64, 18 at Q = 128. More
#: tiles a step read 2 % less time a call on the chip and compile four times as long (PERF.md §6, PR 34)
TILES = 16
#: handed to Mosaic in place of its 16 MiB default (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 32 * 2**20
#: the widest tile the pair takes: beyond it a step's values outgrow `VMEM_LIMIT_BYTES`
WIDEST = 128

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def inverse_fits(device_kind: str, q: int, dtype) -> bool:
    """Whether the kernel pair takes tiles of ``q x q``: traced for TPUs, float32, ``q`` whole sublane tiles of
    8 rows and no wider than `WIDEST`."""
    return device_kind.upper().startswith("TPU") and jnp.dtype(dtype) == _F32 and q % 8 == 0 and 0 < q <= WIDEST


def _products(lhs, rhs):
    """``lhs[g] @ rhs[g]`` for every group of a grid step, float32's own."""
    return jnp.einsum("gij,gjk->gik", lhs, rhs, precision=_HI, preferred_element_type=_F32)


def _side_by_side(q: int) -> int:
    """Tiles of ``q`` columns that share the 128 lanes."""
    return _LANES // q if _LANES % q == 0 else 1


def _inverse_kernel(a_ref, t_ref, *, total: int):
    tiles, q, _ = a_ref.shape
    abreast = _side_by_side(q)
    groups = tiles // abreast
    a = a_ref[...]
    if total % tiles:  # the last grid step's spare tiles
        first = pl.program_id(0) * tiles
        a = jnp.where(first + lax.broadcasted_iota(jnp.int32, (tiles, 1, 1), 0) < total, a, 0.0)
    # [groups, Q, abreast · Q]: tile j · groups + g is group g's j-th
    a = jnp.concatenate([a[j * groups:(j + 1) * groups] for j in range(abreast)], axis=-1)
    width = abreast * q
    rows = lax.broadcasted_iota(jnp.int32, (1, q, width), 1)
    lanes = lax.broadcasted_iota(jnp.int32, (1, 1, width), 2)

    def block_diagonal(x):  # [groups, Q, width] -> [groups, width, width]: tile j in rows and columns j·Q …
        return jnp.concatenate([jnp.where(lanes // q == j, x, 0.0) for j in range(abreast)], axis=1)

    power = -a
    inverse = (rows == lanes % q).astype(_F32) + power
    power = _products(power, block_diagonal(power))
    span = 2  # `inverse` holds the powers below `span`, `power` is the one at `span`
    while 2 * span < q:
        both = _products(jnp.concatenate([power, inverse], axis=1), block_diagonal(power))
        power, inverse = both[:, :q], inverse + both[:, q:]  # the next power; this one's factor taken
        span *= 2
    inverse = inverse + _products(inverse, block_diagonal(power))
    for j in range(abreast):
        t_ref[j * groups:(j + 1) * groups] = inverse[:, :, j * q:(j + 1) * q]


def _gradient_kernel(t_ref, dt_ref, da_ref):
    transposed = jnp.swapaxes(t_ref[...], 1, 2)
    da_ref[...] = -_products(transposed, _products(dt_ref[...], transposed))


def _call(name: str, kernel, *operands, interpret: bool):
    n, q, _ = operands[0].shape
    tiles = pl.BlockSpec((TILES, q, q), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, TILES),),
        in_specs=[tiles] * len(operands),
        out_specs=tiles,
        out_shape=jax.ShapeDtypeStruct((n, q, q), _F32),
        compiler_params=_PARAMS,
        name=name,
        interpret=interpret,
    )(*operands)


def inverse(a, *, interpret: bool = False):
    """``(I + a)⁻¹`` for ``a [N, Q, Q]`` float32, strictly lower triangular: ``dtpu_gdn_inverse``."""
    return _call("dtpu_gdn_inverse", functools.partial(_inverse_kernel, total=a.shape[0]), a, interpret=interpret)


def inverse_bwd(inverse, d_inverse, *, interpret: bool = False):
    """``da = −Tᵀ dT Tᵀ`` for ``T, dT [N, Q, Q]`` float32: ``dtpu_gdn_inverse_bwd``."""
    return _call("dtpu_gdn_inverse_bwd", _gradient_kernel, inverse, d_inverse, interpret=interpret)
