"""Kimi delta attention: the delta rule with a decay per channel, computed chunk by chunk.

Per head, with a key ``k_t`` and a query ``q_t`` of width K, a value ``v_t`` of
width V, a log-decay ``g_t`` in R^K (one a key channel, each in
``(lower_bound, 0)``) and a write strength ``β_t`` in [0, 1], on a state ``S``
in R^{K x V} that starts at zero (Kimi Linear, arXiv:2510.26692)::

    S' = Diag(exp g_t) S_{t-1}
    S_t = S' + k_t ⊗ β_t (v_t − S'ᵀ k_t)
    o_t = S_tᵀ q_t

This is `ops/gdn.py`'s gated delta rule with ``g_t`` a vector where that one
has a scalar a head, and the chunked form is the same (the WY representation;
`ops/gdn.py`'s docstring): inside a chunk of Q steps, with ``Γ_t`` the
inclusive cumulative sum of ``g`` from the chunk's start, ``γ_t = exp(Γ_t)``
and ``S_0`` the state entering it,

    (I + A) U = β ⊙ V − (β ⊙ γ ⊙ K) S_0,      A[t, s] = β_t Σ_c k_t,c k_s,c exp(Γ_t,c − Γ_s,c)  for s < t
    O = (γ ⊙ Q) S_0 + W U,                     W[t, s] = Σ_c q_t,c k_s,c exp(Γ_t,c − Γ_s,c)      for s <= t
    S_Q = Diag(γ_Q) S_0 + (exp(Γ_Q − Γ) ⊙ K)ᵀ U

and ``(I + A)⁻¹`` is `ops.gdn.unit_lower_inverse` as it is, its kernel pair
included. What differs is the decay between two positions: one exponent a
channel, so no ``[Q, Q]`` matrix holds it, and formed pair by pair it would be
a ``[Q, Q, K]`` tensor a chunk and head (8.6 GB for one row of 8192 positions
and 32 heads). So it is factored through a reference position ``r`` between
the two, ``exp(Γ_t − Γ_s) = exp(Γ_t − Γ_r) · exp(Γ_r − Γ_s)``, and ``A`` and
``W`` are products over the channels of keys (or queries) and keys that carry
one factor each. The chunk's rows go in sub-chunks of `SUB` positions, each
with its first position as ``r``: for a key ``s`` of an earlier sub-chunk both
factors are at most 1; inside a row's own sub-chunk ``exp(Γ_r − Γ_s)`` grows,
to at most ``exp(−lower_bound · (SUB − 1))``, which the gate's bound keeps
finite (``exp(5 · 15)``; float32 overflows at ``exp(88.7)``). Keys after the
row's sub-chunk are never formed, so neither pass meets an infinity. These two
products take float32 operands at `lax.Precision.HIGHEST`, since their
operands carry the decays. ``β`` and the strict triangle of ``A`` are applied
after them, and autodiff of that gives ``dβ`` and the products' gradient.

One algorithm, two realisations of the products (`within_chunk_products`),
picked by `_takes_the_kernels` where the call is traced: inside the trainer's
steps on TPUs at tiles `kda_terms.fits` admits, the kernel pair of
`ops/kda_terms.py`, which forms the factors a sub-chunk at a time in VMEM and
computes the products' gradient in its backward kernel from ``q``, ``k`` and
``Γ`` alone (journal ``counters`` ``kda_terms_kernel_calls``); elsewhere XLA's
batched products, each factor a ``[.., Q, K]`` or ``[.., Q/SUB, Q, K]`` tensor
through HBM, their exponent masked to 0 beyond a sub-chunk's end before the
``exp`` (``kda_terms_xla_calls`` inside a mesh, uncounted outside).

Float32: the log-decays and their cumulative sums, every ``exp``, ``A``, ``W``,
the inverse and the carried state. The products with the values and the state
take operands of ``v.dtype`` and accumulate in float32, as in `ops/gdn.py`. A
length that is no multiple of the chunk is padded with steps of ``g = 0``,
``β = 0``, which change no state; their outputs are dropped. The whole rule
stands under the step scope ``dtpu.kda_scan``.

A chunk's tensors (a score of ``[Q, K]``-sized ones and a few ``[Q, Q]``) are
formed for groups of chunks at a time (`ops.rows.rows_in_groups` over the
chunks, each group rematerialised); only the sequential part, a `lax.scan`
over the chunks, sees all of a row at once.

``tests/test_bailing_hybrid.py`` holds the recurrence as a `lax.scan` over
time, which the chunked form is tested against, values and gradients.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops import kda_terms
from distribuuuu_tpu.ops.gdn import unit_lower_inverse
from distribuuuu_tpu.ops.interpret import pallas_interpret
from distribuuuu_tpu.ops.rows import rows_in_groups

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
#: positions of a sub-chunk: the rows that share a reference position, and how far a decay is undone inside one
SUB = kda_terms.SUB
#: float32 tensors of a ``[Q, max(K, V)]``'s size, and of a ``[Q, Q]``'s, that one chunk and head holds at a time
CHUNK_WIDE, CHUNK_SQUARE = 18, 8
#: `jax.monitoring` events, one a traced `within_chunk_products` inside a mesh: which realisation it took. The
#: journal's ``counters`` records carry them (obs/monitors.py)
KERNEL_CALLS_EVENT = "kda_terms_kernel_calls"
XLA_CALLS_EVENT = "kda_terms_xla_calls"


def kimi_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """Chunked Kimi delta rule.

    ``q, k [B, L, H, K]`` (already normalised and scaled as the model wants
    them); ``v [B, L, H, V]``; ``g [B, L, H, K]`` float32 log-decays in
    ``(lower_bound, 0)`` with ``−lower_bound · (SUB − 1)`` under float32's
    largest exponent; ``beta [B, L, H]`` float32 in [0, 1]. Returns
    ``o [B, L, H, V]`` in ``v.dtype``.
    """
    with step_scope("kda_scan"):
        batch, length, heads, kd = k.shape
        vd = v.shape[-1]
        dtype = v.dtype
        if chunk % SUB:
            raise ValueError(f"a chunk of {chunk} positions is no whole number of sub-chunks of {SUB}")
        pad = (-length) % chunk
        if pad:
            widths = lambda t: [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)
            q, k, v, g, beta = (jnp.pad(t, widths(t)) for t in (q, k, v, g, beta))
        nc = (length + pad) // chunk
        # [B, L, H, ...] -> [C·B, H, Q, ...]: a chunk of a row is a row of the terms' groups, the chunks leading
        chunks = lambda t: jnp.moveaxis(t.reshape(batch, nc, chunk, heads, *t.shape[3:]), (1, 3), (0, 2)).reshape(
            nc * batch, heads, chunk, *t.shape[3:])
        series = (chunks(q.astype(dtype)), chunks(k.astype(dtype)), chunks(v), chunks(g.astype(_F32)),
                  chunks(beta.astype(_F32)))
        chunk_bytes = 4 * heads * (CHUNK_WIDE * chunk * max(kd, vd) + CHUNK_SQUARE * chunk * chunk)
        group_bytes = 1 << (chunk_bytes - 1).bit_length()  # a power of two: the groups divide a power-of-two count
        terms = rows_in_groups(_chunk_terms, series, group_bytes, remat=True)
        own, reads, within, q_gamma, k_to_end, gamma_end = (t.reshape(nc, batch, *t.shape[1:]) for t in terms)

        def one_chunk(state, per_chunk):
            own_c, reads_c, within_c, q_gamma_c, k_to_end_c, gamma_end_c = per_chunk
            entering = state.astype(dtype)
            u = (own_c - jnp.einsum("bhtk,bhkv->bhtv", reads_c, entering, preferred_element_type=_F32)).astype(dtype)
            out = (jnp.einsum("bhtk,bhkv->bhtv", q_gamma_c, entering, preferred_element_type=_F32)
                   + jnp.einsum("bhts,bhsv->bhtv", within_c, u, preferred_element_type=_F32))
            state = (gamma_end_c[..., None] * state
                     + jnp.einsum("bhsk,bhsv->bhkv", k_to_end_c, u, preferred_element_type=_F32))
            return state, out.astype(dtype)

        zeros = jnp.zeros((batch, heads, kd, vd), _F32)
        # a chunk keeps the state that enters it and no more for the backward pass: its few products run again there
        _, out = lax.scan(jax.checkpoint(one_chunk), zeros, (own, reads, within, q_gamma, k_to_end, gamma_end))
        out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(batch, nc * chunk, heads, vd)  # [C,B,H,Q,V] -> [B,L,H,V]
        return out[:, :length]


def _chunk_terms(q, k, v, g, beta):
    """What the scan over chunks reads of each chunk ``[N, H, Q, ·]``: ``T(βV)``, ``T(βγK)``, ``W``, ``γ ⊙ Q``,
    ``exp(Γ_Q − Γ) ⊙ K`` and ``γ_Q``, with ``T = (I + A)⁻¹``."""
    dtype = v.dtype
    chunk = k.shape[-2]
    cum = jnp.cumsum(g, axis=-2)                                          # Γ, inclusive, within the chunk [N, H, Q, K]
    p, within = within_chunk_products(q, k, cum)
    s = jnp.arange(chunk)
    a = jnp.where(s[:, None] > s[None, :], p * beta[..., None], 0.0)
    t_inv = unit_lower_inverse(a).astype(dtype)                           # [N, H, Q, Q]

    gamma = jnp.exp(cum)
    v_beta = (v.astype(_F32) * beta[..., None]).astype(dtype)
    k32 = k.astype(_F32)
    k_beta_gamma = (k32 * beta[..., None] * gamma).astype(dtype)
    own = jnp.einsum("...ts,...sv->...tv", t_inv, v_beta, preferred_element_type=_F32)                  # T(βV)
    reads = jnp.einsum("...ts,...sk->...tk", t_inv, k_beta_gamma, preferred_element_type=_F32).astype(dtype)  # T(βγK)
    q_gamma = (q.astype(_F32) * gamma).astype(dtype)
    k_to_end = (k32 * jnp.exp(cum[..., -1:, :] - cum)).astype(dtype)
    return own, reads, within, q_gamma, k_to_end, jnp.exp(cum[..., -1, :])


def _takes_the_kernels(k) -> bool:
    """The realisation of the within-chunk products for ``k [..., Q, K]``, from what the trace can observe:
    `ops/kda_terms.py`'s kernels where a mesh of TPUs is in use (the described chips of a compile-only test count
    as what they describe) and `kda_terms.fits` admits the tile; outside any mesh (``model.init``, shape
    inference, a test's plain call) XLA's products, uncounted."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return False
    fits = kda_terms.fits(mesh.abstract_device.device_kind, k.shape[-2], k.shape[-1])
    jax.monitoring.record_event(KERNEL_CALLS_EVENT if fits else XLA_CALLS_EVENT)
    return fits


def within_chunk_products(q, k, cum):
    """``P`` and ``W`` of every chunk and head for ``q, k [..., Q, K]`` and ``Γ`` ``cum [..., Q, K]`` float32:
    ``P[t, s] = Σ_c k_t,c k_s,c exp(Γ_t,c − Γ_s,c)`` float32 for ``s`` up to the end of ``t``'s sub-chunk (zero
    beyond), ``W`` the same with ``q_t`` for ``s <= t`` (zero above), in ``q``'s dtype. One algorithm, two
    realisations, picked by `_takes_the_kernels` where the call is traced."""
    if _takes_the_kernels(k):
        return _kernel_products(q, k, cum, pallas_interpret())
    return _xla_products(q, k, cum)


def _xla_products(q, k, cum):
    """XLA's realisation: the factors through each sub-chunk's first position as ``[.., Q, K]`` and
    ``[.., Q/SUB, Q, K]`` tensors, and batched products of them; autodiff's backward pass."""
    chunk, width = k.shape[-2:]
    sub = cum.reshape(*cum.shape[:-2], chunk // SUB, SUB, width)         # [.., n, SUB, K]
    ref = sub[..., :1, :]                                                 # Γ_r: each sub-chunk's first position
    from_ref = jnp.exp(sub - ref).reshape(cum.shape)                      # exp(Γ_t − Γ_r) <= 1 for a row t
    k32 = k.astype(_F32)
    q_rows, k_rows = (t.astype(_F32) * from_ref for t in (q, k))
    # exp(Γ_r − Γ_s) for each sub-chunk's reference and every key s up to the sub-chunk's end; masked to 0 after it
    s = jnp.arange(chunk)
    reach = s[None, :] < SUB * (jnp.arange(chunk // SUB)[:, None] + 1)   # [n, Q]
    to_ref = jnp.exp(jnp.where(reach[:, :, None], ref - cum[..., None, :, :], 0.0))  # [.., n, Q, K]
    k_cols = k32[..., None, :, :] * to_ref
    by_sub = lambda rows: jnp.einsum("...ntk,...nsk->...nts",
                                     rows.reshape(*rows.shape[:-2], chunk // SUB, SUB, width), k_cols,
                                     precision=_HI, preferred_element_type=_F32).reshape(*rows.shape[:-1], chunk)
    p = jnp.where(jnp.repeat(reach, SUB, axis=0), by_sub(k_rows), 0.0)   # zero beyond the row's sub-chunk
    return p, jnp.where(s[:, None] >= s[None, :], by_sub(q_rows), 0.0).astype(q.dtype)  # s <= t, the diagonal kept


def _flat(t):
    return t.reshape(-1, *t.shape[-2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_products(q, k, cum, interpret: bool):
    """The kernels' realisation, over the tiles of the leading axes flattened; `ops/kda_terms.py`'s backward
    kernel is its backward pass, which keeps ``q``, ``k`` and ``Γ`` alone."""
    p, w = kda_terms.forward(_flat(q), _flat(k), _flat(cum), interpret=interpret)
    square = (*k.shape[:-1], k.shape[-2])
    return p.reshape(square), w.reshape(square)


def _kernel_products_fwd(q, k, cum, interpret):
    return _kernel_products(q, k, cum, interpret), (q, k, cum)


def _kernel_products_bwd(interpret, residuals, grads):
    q, k, cum = residuals
    dp, dw = grads
    dq, dk, dcum = kda_terms.backward(_flat(q), _flat(k), _flat(cum), _flat(dp), _flat(dw), interpret=interpret)
    return dq.reshape(q.shape), dk.reshape(k.shape), dcum.reshape(cum.shape)


_kernel_products.defvjp(_kernel_products_fwd, _kernel_products_bwd)
