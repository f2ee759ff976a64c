"""Grouped matrix products for rows sorted by expert into blocks, one expert a block.

`parallel.moe.held_experts` lays the token-expert slots that landed on the
experts held here out in blocks of ``block`` rows that belong to one expert
each (an expert's last block padded, the blocks past the end of the layout
dead). Its two products multiply every block by its own expert's weights. Two
Mosaic kernels do that with the weights where they lie, ``[held, K, N]``:

- ``dtpu_moe_gmm``: ``out[b] = rows[b] @ w[expert[b]]`` (or ``@ w[expert[b]]ᵀ``
  for the input gradient). The weight's `BlockSpec` takes ``expert[b]`` from
  scalar-prefetched memory, so a block reads its expert's tile straight from
  the held array, and consecutive blocks of one expert read it once. Blocks at
  or beyond ``live_blocks`` are not computed and come back as zeros.
- ``dtpu_moe_tgmm``: ``dw[e] = Σ_{b of e} lhs[b]ᵀ @ rhs[b]`` in float32. The
  blocks of an expert are consecutive, so its gradient tile stays in VMEM while
  they are added and crosses HBM once; an expert with no live block is visited
  once all the same and gets zeros.

`grouped_product` ties them with one `jax.custom_vjp`. The weights are cast to
the rows' dtype once a call for all held experts (an XLA convert: at the
deployment's batch the blocks grow and the cast does not); the products take
bfloat16 operands and accumulate in float32, and the weight gradient is summed
in float32 over an expert's blocks before it is ever rounded. Every kernel
call, forward and backward, stands under the step's scope ``dtpu.moe_experts``.
`grouped_product_fuses` says from the device kind and the shapes whether the
kernels can take a product; `parallel.moe` asks it and keeps XLA's batched
products otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distribuuuu_tpu.obs.trace import step_scope

#: what the tiles of one grid step may take together: operands and results
#: double-buffered, and the product before it is stored
TILE_VMEM_BYTES = 24 * 2**20
#: handed to Mosaic in place of its 16 MiB default (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 40 * 2**20

_F32 = jnp.float32


def _lane_tiles(n: int) -> list[int]:
    """The widths that tile ``n`` in whole 128-lane groups, widest first."""
    return [t for t in range(n, 0, -128) if n % t == 0] if n % 128 == 0 else []


def gmm_tile(block: int, k: int, n: int, itemsize: int) -> int | None:
    """Width of `dtpu_moe_gmm`'s weight and result tile: the widest whose grid step fits, or None."""
    fits = lambda t: 2 * k * t * itemsize + 2 * block * k * itemsize + 3 * block * t * 4 <= TILE_VMEM_BYTES
    return next(filter(fits, _lane_tiles(n)), None)


def tgmm_tile(block: int, k: int, n: int, itemsize: int) -> int | None:
    """Width of `dtpu_moe_tgmm`'s float32 gradient tile ``[k, width]``, or None."""
    fits = lambda t: 3 * k * t * 4 + 2 * block * (k + t) * itemsize <= TILE_VMEM_BYTES
    return next(filter(fits, _lane_tiles(n)), None)


def grouped_product_fuses(device_kind: str, block: int, k: int, n: int, itemsize: int) -> bool:
    """Whether the kernel pair takes ``rows [·, k] @ w [·, k, n]`` in blocks of ``block`` rows, forward
    and backward: traced for TPUs, both widths whole 128-lane groups, the row block whole sublane
    tiles of the rows' dtype, and a tile of each of the four calls inside `TILE_VMEM_BYTES`."""
    return (
        device_kind.upper().startswith("TPU")
        and block % (8 * 4 // itemsize) == 0
        and None not in (gmm_tile(block, k, n, itemsize), gmm_tile(block, n, k, itemsize),
                         tgmm_tile(block, k, n, itemsize), tgmm_tile(block, n, k, itemsize))
    )


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _gmm_kernel(expert_ref, live_ref, rows_ref, w_ref, out_ref, *, transposed: bool):
    del expert_ref  # the index maps read it
    live = pl.program_id(1) < live_ref[0]

    @pl.when(live)
    def _():
        dims = (((1,), (1 if transposed else 0,)), ((), ()))
        out_ref[...] = lax.dot_general(rows_ref[...], w_ref[...], dims,
                                       preferred_element_type=_F32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def gmm(rows, expert, live_blocks, w, *, transposed: bool = False, out_dtype=_F32, interpret: bool = False):
    """``out[b] = rows[b] @ w[expert[b]]`` for the blocks ``b < live_blocks``, zeros for the rest.

    ``rows [R, K]`` and ``w [H, K, N]`` of one dtype (``[H, N, K]`` with ``transposed``: the product
    is then with the weight's transpose); ``expert [R / block] int32`` not falling over the live
    blocks; ``live_blocks`` an int32 scalar. Returns ``[R, N]``, summed in float32 and rounded to
    ``out_dtype`` once."""
    r, k = rows.shape
    blocks = expert.shape[0]
    block = r // blocks
    n = w.shape[1] if transposed else w.shape[2]
    tile = gmm_tile(block, k, n, rows.dtype.itemsize)
    if tile is None:
        raise ValueError(f"dtpu_moe_gmm: no tile for blocks of {block} rows, K {k}, N {n}")

    def last_live(b, live):  # a dead block asks for what the last live one held: nothing is fetched for it
        return jnp.minimum(b, jnp.maximum(live[0] - 1, 0))

    w_spec = (pl.BlockSpec((None, tile, k), lambda j, b, e, live: (e[last_live(b, live)], j, 0)) if transposed
              else pl.BlockSpec((None, k, tile), lambda j, b, e, live: (e[last_live(b, live)], 0, j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tile, blocks),
            in_specs=[pl.BlockSpec((block, k), lambda j, b, e, live: (last_live(b, live), 0)), w_spec],
            out_specs=pl.BlockSpec((block, tile), lambda j, b, e, live: (b, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        compiler_params=_PARAMS,
        name="dtpu_moe_gmm",
        interpret=interpret,
    )(expert.astype(jnp.int32), jnp.reshape(live_blocks, (1,)).astype(jnp.int32), rows, w)


def _visits(expert, live_blocks, held: int):
    """The order in which `dtpu_moe_tgmm` walks a round: every expert in turn, its live blocks one
    after the other, an expert with none once (its gradient has to become zeros); then idle steps
    up to the static ``blocks + held``. Returns ``(expert, block, real)`` a step, int32: the
    gradient tile it holds, the block it reads (an idle or empty step names a neighbour's, so
    nothing new is fetched) and whether it adds a product."""
    blocks = expert.shape[0]
    mine = (expert[None, :] == jnp.arange(held)[:, None]) & (jnp.arange(blocks) < live_blocks)[None, :]
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)  # live blocks an expert
    steps = jnp.maximum(count, 1)
    ends = jnp.cumsum(steps)
    v = jnp.arange(blocks + held, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(ends, v, side="right"), held - 1).astype(jnp.int32)
    k = v - (ends - steps)[e]
    real = (k < count[e]) & (v < ends[-1])
    first = (jnp.cumsum(count) - count)[e]
    block = jnp.clip(first + jnp.minimum(k, jnp.maximum(count[e] - 1, 0)), 0, jnp.maximum(live_blocks - 1, 0))
    return e, block.astype(jnp.int32), real.astype(jnp.int32)


def _tgmm_kernel(expert_ref, block_ref, real_ref, lhs_ref, rhs_ref, out_ref):
    del block_ref  # the index maps read it
    v = pl.program_id(1)

    @pl.when(jnp.logical_or(v == 0, expert_ref[jnp.maximum(v - 1, 0)] != expert_ref[v]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(real_ref[v] != 0)
    def _():
        out_ref[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
                                        preferred_element_type=_F32)


def tgmm(lhs, rhs, expert, live_blocks, held: int, *, interpret: bool = False):
    """``dw[e] = Σ lhs[b]ᵀ @ rhs[b]`` over the live blocks of expert ``e``, ``[held, K, N]`` float32;
    zeros for an expert with none. ``lhs [R, K]``, ``rhs [R, N]`` of one dtype; ``expert`` and
    ``live_blocks`` as `gmm` takes them."""
    r, k = lhs.shape
    n = rhs.shape[1]
    blocks = expert.shape[0]
    block = r // blocks
    tile = tgmm_tile(block, k, n, lhs.dtype.itemsize)
    if tile is None:
        raise ValueError(f"dtpu_moe_tgmm: no tile for blocks of {block} rows, K {k}, N {n}")
    live_blocks = jnp.asarray(live_blocks, jnp.int32)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tile, blocks + held),
            in_specs=[pl.BlockSpec((block, k), lambda j, v, e, b, real: (b[v], 0)),
                      pl.BlockSpec((block, tile), lambda j, v, e, b, real: (b[v], j))],
            out_specs=pl.BlockSpec((None, k, tile), lambda j, v, e, b, real: (e[v], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((held, k, n), _F32),
        compiler_params=_PARAMS,
        name="dtpu_moe_tgmm",
        interpret=interpret,
    )(*_visits(expert.astype(jnp.int32), live_blocks, held), lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_product(rows, expert, live_blocks, w, transposed: bool = False, interpret: bool = False):
    """``rows[b] @ w[expert[b]]`` block by block (`gmm`), differentiable in ``rows`` and ``w``.

    ``rows [R, K]``; ``w [H, K, N]`` in any float dtype (``[H, N, K]`` with ``transposed``), cast to
    the rows' once; the result ``[R, N]`` float32. The gradients: `gmm` with the weight the other way
    round, in the rows' dtype, and `tgmm` in float32 (then ``w``'s dtype)."""
    return _grouped_fwd(rows, expert, live_blocks, w, transposed, interpret)[0]


def _grouped_fwd(rows, expert, live_blocks, w, transposed, interpret):
    with step_scope("moe_experts"):
        cast = w.astype(rows.dtype)
        out = gmm(rows, expert, live_blocks, cast, transposed=transposed, interpret=interpret)
    return out, (rows, expert, live_blocks, cast, jnp.zeros((0,), w.dtype))  # the last: w's dtype, for its gradient


def _grouped_bwd(transposed, interpret, residuals, d_out):
    rows, expert, live_blocks, cast, w_like = residuals
    with step_scope("moe_experts"):  # its own: a backward rule is not certain to inherit the caller's scope
        d_out = d_out.astype(rows.dtype)
        d_rows = gmm(d_out, expert, live_blocks, cast, transposed=not transposed, out_dtype=rows.dtype,
                     interpret=interpret)
        lhs, rhs = (d_out, rows) if transposed else (rows, d_out)
        d_w = tgmm(lhs, rhs, expert, live_blocks, cast.shape[0], interpret=interpret)
        return d_rows, None, None, d_w.astype(w_like.dtype)


grouped_product.defvjp(_grouped_fwd, _grouped_bwd)
