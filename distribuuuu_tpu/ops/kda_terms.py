"""The Kimi delta rule's within-chunk products (`ops.kda`) with their decay factors formed in VMEM.

Per chunk and head, with ``Γ`` the inclusive cumulative sum of the log-decays ``[Q, K]`` and the chunk's
rows in sub-chunks of `SUB` positions, each with its first position ``r`` as reference (`ops/kda.py`'s
docstring), a row ``t`` of sub-chunk ``j`` and a key ``s`` up to the sub-chunk's end ``e_j``::

    R_t = x_t ⊙ exp(Γ_t − Γ_r)          (x = k for P, q for W; at most 1)
    C_s = k_s ⊙ exp(Γ_r − Γ_s)          (at most exp(−lower_bound · (SUB − 1)) inside the sub-chunk)
    P[t, s] = Σ_c R^k_t,c C_s,c,        W[t, s] = Σ_c R^q_t,c C_s,c   (s <= t)

Formed as XLA's batched products, each factor is a ``[.., Q, K]`` or ``[.., Q/SUB, Q, K]`` float32
tensor that crosses HBM, three times a step. Two Mosaic kernels keep them on the chip, `TILES`
(chunk, head) tiles a grid step, a sub-chunk at a time:

- ``dtpu_kda_terms``: ``q``, ``k`` ``[N, Q, K]`` (the compute dtype) and ``Γ`` ``[N, Q, K]`` float32 in;
  ``P`` ``[N, Q, Q]`` float32, zero beyond each row's sub-chunk, and ``W`` ``[N, Q, Q]`` in ``q``'s dtype,
  zero above the diagonal, out. A sub-chunk's rows of keys and queries go through the matrix unit as one
  product of ``2 · SUB`` rows against the keys up to the sub-chunk's end.
- ``dtpu_kda_terms_bwd``: ``q``, ``k``, ``Γ``, ``dP`` (float32) and ``dW`` in (five operands, the most
  `benchmark/hlo.kernel_calls` reads); the factors again, then per sub-chunk, with ``G = [dP; dW]`` its
  rows (``dW`` masked to ``s <= t``) and ``R = [R^k; R^q]``, ``dR = G · C`` and ``dC = Gᵀ · R``; out
  ``dq``, ``dk`` (``q``'s and ``k``'s dtypes) and ``dΓ`` float32: ``dR ⊙ exp(Γ_t − Γ_r)`` at the rows,
  ``dC ⊙ exp(Γ_r − Γ_s)`` at the keys, and for ``Γ`` the factors' own ``±dR ⊙ R`` and ``∓dC ⊙ C`` at
  ``t``, ``s`` and the reference ``r``.

Every product takes float32 operands at `lax.Precision.HIGHEST` and accumulates in float32, as the
configuration's ``precision`` states for the within-chunk matrices. `fits` says from the device kind and
the shape whether the pair takes a call; `ops.kda` asks it and keeps XLA's products otherwise. A tile count
that is no multiple of `TILES` leaves the last grid step spare tiles, whose reads are whatever the buffer
held and whose writes are dropped; no product mixes two tiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_LANES = 128
#: positions of a sub-chunk (`ops.kda.SUB`)
SUB = 16
#: (chunk, head) tiles a grid step: 16 read 7 % less time a forward call than 8, and 16 % less than 4 (PERF.md §5)
TILES = 16
#: handed to Mosaic in place of its 16 MiB default (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 48 * 2**20
#: the longest chunk the pair takes: a step's values grow with it
LONGEST = 128

FWD_NAME = "dtpu_kda_terms"
BWD_NAME = "dtpu_kda_terms_bwd"

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def fits(device_kind: str, q: int, k: int) -> bool:
    """Whether the pair takes chunks of ``q`` positions with keys ``k`` wide: traced for TPUs, a whole number
    of sub-chunks no longer than `LONGEST`, and keys of whole 128-lane groups."""
    return device_kind.upper().startswith("TPU") and q % SUB == 0 and 0 < q <= LONGEST and k % _LANES == 0


def _products(spec: str, a, b):
    """A float32 product of every tile of a grid step, float32's own."""
    return jnp.einsum(spec, a, b, precision=_HI, preferred_element_type=_F32)


def _factors(cum, j: int):
    """Sub-chunk ``j``'s rows' ``exp(Γ_t − Γ_r) [T, SUB, K]`` and its keys' ``exp(Γ_r − Γ_s) [T, e_j, K]`` up to
    its end ``e_j``, ``r`` its first position."""
    ref = cum[:, SUB * j:SUB * j + 1]
    return jnp.exp(cum[:, SUB * j:SUB * (j + 1)] - ref), jnp.exp(ref - cum[:, :SUB * (j + 1)])


def _on_or_below(j: int, end: int):
    """``s <= t`` for sub-chunk ``j``'s rows against the keys up to its end ``[1, SUB, end]``."""
    t = SUB * j + lax.broadcasted_iota(jnp.int32, (1, SUB, end), 1)
    return lax.broadcasted_iota(jnp.int32, (1, SUB, end), 2) <= t


def _fwd_kernel(q_ref, k_ref, cum_ref, p_ref, w_ref):
    tiles, chunk, _ = k_ref.shape
    q, k, cum = q_ref[...].astype(_F32), k_ref[...].astype(_F32), cum_ref[...]
    for j in range(chunk // SUB):
        end, rows = SUB * (j + 1), slice(SUB * j, SUB * (j + 1))
        from_ref, to_ref = _factors(cum, j)
        rows_of_both = jnp.concatenate([k[:, rows], q[:, rows]], axis=1) * jnp.concatenate([from_ref] * 2, axis=1)
        both = _products("tik,tjk->tij", rows_of_both, k[:, :end] * to_ref)  # [T, 2·SUB, e_j]
        p_ref[:, rows, :end] = both[:, :SUB]
        w_ref[:, rows, :end] = jnp.where(_on_or_below(j, end), both[:, SUB:], 0.0).astype(w_ref.dtype)
        if end < chunk:
            p_ref[:, rows, end:] = jnp.zeros((tiles, SUB, chunk - end), _F32)
            w_ref[:, rows, end:] = jnp.zeros((tiles, SUB, chunk - end), w_ref.dtype)


def _bwd_kernel(q_ref, k_ref, cum_ref, dp_ref, dw_ref, dq_ref, dk_ref, dcum_ref):
    tiles, chunk, width = k_ref.shape
    q, k, cum = q_ref[...].astype(_F32), k_ref[...].astype(_F32), cum_ref[...]
    dk = jnp.zeros((tiles, chunk, width), _F32)
    dcum = jnp.zeros((tiles, chunk, width), _F32)

    def placed(x, start: int):  # [T, n, K] at rows start … start + n of a chunk's [T, Q, K], zeros elsewhere
        parts = [jnp.zeros((tiles, start, width), _F32)] if start else []
        rest = chunk - start - x.shape[1]
        return jnp.concatenate(parts + [x] + ([jnp.zeros((tiles, rest, width), _F32)] if rest else []), axis=1)

    dq_rows = []
    for j in range(chunk // SUB):
        end, rows = SUB * (j + 1), slice(SUB * j, SUB * (j + 1))
        from_ref, to_ref = _factors(cum, j)
        rk, rq, cols = k[:, rows] * from_ref, q[:, rows] * from_ref, k[:, :end] * to_ref
        g = jnp.concatenate([dp_ref[:, rows, :end],
                             jnp.where(_on_or_below(j, end), dw_ref[:, rows, :end].astype(_F32), 0.0)], axis=1)
        d_rows = _products("tij,tjk->tik", g, cols)                       # [T, 2·SUB, K]
        d_cols = _products("tji,tjk->tik", g, jnp.concatenate([rk, rq], axis=1))  # [T, e_j, K]
        dk_rows, dq_row = d_rows[:, :SUB], d_rows[:, SUB:]
        dq_rows.append(dq_row * from_ref)
        by_rows = dk_rows * rk + dq_row * rq                              # d/dΓ_t of the rows' factor
        by_cols = d_cols * cols                                           # −d/dΓ_s of the keys' factor
        # the reference's share: −Σ_t by_rows + Σ_s by_cols, added at the sub-chunk's first position
        at_ref = jnp.sum(by_cols, axis=1, keepdims=True) - jnp.sum(by_rows, axis=1, keepdims=True)
        first = lax.broadcasted_iota(jnp.int32, (1, SUB, 1), 1) == 0
        dcum = dcum - placed(by_cols, 0) + placed(jnp.where(first, at_ref, 0.0) + by_rows, SUB * j)
        dk = dk + placed(d_cols * to_ref, 0) + placed(dk_rows * from_ref, SUB * j)
    dq_ref[...] = jnp.concatenate(dq_rows, axis=1).astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dcum_ref[...] = dcum


def _tiles(shape):
    return pl.BlockSpec((TILES, *shape[1:]), lambda i: (i, 0, 0))


def forward(q, k, cum, *, interpret: bool = False):
    """``(P, W)`` for ``q, k [N, Q, K]`` and ``cum [N, Q, K]`` float32: ``dtpu_kda_terms``."""
    n, chunk, _ = k.shape
    square = (n, chunk, chunk)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(pl.cdiv(n, TILES),),
        in_specs=[_tiles(q.shape), _tiles(k.shape), _tiles(cum.shape)],
        out_specs=(_tiles(square), _tiles(square)),
        out_shape=(jax.ShapeDtypeStruct(square, _F32), jax.ShapeDtypeStruct(square, q.dtype)),
        compiler_params=_PARAMS,
        name=FWD_NAME,
        interpret=interpret,
    )(q, k, cum)


def backward(q, k, cum, dp, dw, *, interpret: bool = False):
    """``(dq, dk, dΓ)`` for the operands of `forward` and the gradients of its two results:
    ``dtpu_kda_terms_bwd``."""
    n = k.shape[0]
    return pl.pallas_call(
        _bwd_kernel,
        grid=(pl.cdiv(n, TILES),),
        in_specs=[_tiles(t.shape) for t in (q, k, cum, dp, dw)],
        out_specs=(_tiles(q.shape), _tiles(k.shape), _tiles(cum.shape)),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(cum.shape, _F32)),
        compiler_params=_PARAMS,
        name=BWD_NAME,
        interpret=interpret,
    )(q, k, cum, dp, dw)
