"""Gated delta rule: the recurrence of Gated DeltaNet linear attention, computed chunk by chunk.

Per head, with a key ``k_t`` and a query ``q_t`` of width K, a value ``v_t`` of
width V, a decay ``α_t = exp(g_t)`` (``g_t <= 0``) and a write strength
``β_t`` in [0, 1], on a state ``S`` in R^{K x V} that starts at zero::

    S' = α_t · S_{t-1}
    S_t = S' + k_t ⊗ β_t (v_t − S'ᵀ k_t)
    o_t = S_tᵀ q_t

Unlike the state-space scan (`ops/ssm.py`), whose state only decays and
accumulates, this one *reads the state before it writes*: what is written at
``t`` is the value less what the decayed state already answers for ``k_t``.
`gated_delta_rule` is the chunked form (Yang et al. 2024, "Gated Delta
Networks"; the WY representation of a product of Householder-like factors).
Inside a chunk of Q steps, with ``γ_t = Π_{s<=t} α_s`` from the chunk's start
and ``S_0`` the state entering it, the written values ``u_t`` obey

    (I + A) U = β ⊙ V − (β ⊙ γ ⊙ K) S_0,      A[t, s] = β_t (γ_t / γ_s)(k_t · k_s)  for s < t, else 0

so they take the inverse of a unit lower-triangular ``Q x Q`` matrix. ``A`` is
strictly lower triangular, so nilpotent: ``(I + A)⁻¹ = Π_{j<log2 Q} (I + N^(2^j))``
with ``N = −A``, which is ``log2 Q − 1`` squarings and as many products
(`unit_lower_inverse`; no substitution, which would be Q dependent steps of
vector work): inside the trainer's steps on TPUs a kernel pair that holds a
chunk's tile in VMEM through the whole chain, forward and backward
(`ops/gdn_inverse.py`), elsewhere XLA's batched products of all chunks at
once, each crossing HBM. Then, a chunk after the other
(a `lax.scan` over the L/Q chunks, the only sequential part)::

    U = T(βV) − T(βγK) S_0                                   T = (I + A)⁻¹
    O = (γ ⊙ Q) S_0 + (Q Kᵀ ⊙ decay, s <= t) U
    S_Q = γ_Q S_0 + (γ_Q / γ ⊙ K)ᵀ U

Float32: the log-decays and their cumulative sums, every ``exp``, ``A`` and
its inverse (float32 products, `lax.Precision.HIGHEST`), the carried state.
The other products take operands of ``v.dtype`` and accumulate in float32. The backward pass is
autodiff's of exactly this. A length that is no multiple of the chunk is
padded with steps of ``g = 0``, ``β = 0`` (no decay, nothing written), which
change no state; the padding's outputs are dropped.

A chunk's tensors exist for all chunks of a row at once (a dozen of
``[L/Q, H, Q, Q]`` or ``[L/Q, H, Q, K]`` apiece), and twice over while the
backward pass runs: the rows go through `ops.rows.rows_in_groups`, each group
rematerialised (at 8192 positions and 32 heads the dozen are 768 MiB a row, so
a row is a group: 2 GB less at the second token cell's peak, PERF.md §5).

``tests/test_qwen3_next.py`` holds the recurrence as a `lax.scan` over time,
which the chunked form is tested against, values and gradients.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops import gdn_inverse
from distribuuuu_tpu.ops.interpret import pallas_interpret
from distribuuuu_tpu.ops.rows import rows_in_groups

_F32 = jnp.float32
#: the inverse's products are float32's own, as the configuration's `precision` states, in the kernels and in XLA's
#: body alike. As XLA's batched products the ten of a chunk's inverse cross HBM one by one (PERF.md §5)
_HI = lax.Precision.HIGHEST
#: tensors of a float32 ``[L/Q, H, Q, Q]``'s size that the chunks of a row hold at once
CHUNK_TENSORS = 12
#: `jax.monitoring` events, one a traced `unit_lower_inverse` inside a mesh: which realisation it took. The
#: journal's ``counters`` records carry them (obs/monitors.py)
KERNEL_CALLS_EVENT = "gdn_inverse_kernel_calls"
XLA_CALLS_EVENT = "gdn_inverse_xla_calls"


def _takes_the_kernels(a) -> bool:
    """The realisation of the inverse of ``a [..., Q, Q]``, from what the trace can observe:
    `ops/gdn_inverse.py`'s kernels where a mesh of TPUs is in use (the described chips of a compile-only test
    count as what they describe) and `inverse_fits` admits the tile; outside any mesh (``model.init``, shape
    inference, a test's plain call) XLA's products, uncounted."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return False
    fits = gdn_inverse.inverse_fits(mesh.abstract_device.device_kind, a.shape[-1], a.dtype)
    jax.monitoring.record_event(KERNEL_CALLS_EVENT if fits else XLA_CALLS_EVENT)
    return fits


def unit_lower_inverse(a):
    """``(I + a)⁻¹`` for ``a [..., Q, Q]`` strictly lower triangular, float32, by the squarings of the
    nilpotent ``−a``: ``(I − n)⁻¹ = (I + n)(I + n²)(I + n⁴) …`` up to the power that vanishes. Its backward pass
    is the inverse's own, ``da = −Tᵀ dT Tᵀ``: two products, and ``T`` alone kept for them (autodiff's of the
    squarings would keep every power and partial product: ten ``Q x Q`` tensors a chunk). One algorithm, two
    realisations, picked by `_takes_the_kernels` where the call is traced."""
    return _inverse(a, _takes_the_kernels(a), pallas_interpret())


def _tile_by_tile(kernel, *operands, interpret: bool):
    """A kernel over ``[N, Q, Q]`` for operands ``[..., Q, Q]``: the leading axes flattened around the call."""
    shape = operands[0].shape
    return kernel(*(t.reshape(-1, *shape[-2:]) for t in operands), interpret=interpret).reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _inverse(a, kernels: bool, interpret: bool):
    if kernels:
        return _tile_by_tile(gdn_inverse.inverse, a, interpret=interpret)
    q = a.shape[-1]
    power = -a
    inverse = jnp.eye(q, dtype=_F32) + power
    span = 2  # `inverse` holds the powers below `span`
    while span < q:
        power = jnp.matmul(power, power, precision=_HI)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HI)
        span *= 2
    return inverse


def _inverse_fwd(a, kernels, interpret):
    inverse = _inverse(a, kernels, interpret)
    return inverse, inverse


def _inverse_bwd(kernels, interpret, inverse, d_inverse):
    if kernels:
        return (_tile_by_tile(gdn_inverse.inverse_bwd, inverse, d_inverse, interpret=interpret),)
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-jnp.matmul(jnp.matmul(transposed, d_inverse, precision=_HI), transposed, precision=_HI),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """Chunked gated delta rule.

    ``q, k [B, L, H, K]`` (already normalised and scaled as the model wants
    them); ``v [B, L, H, V]``; ``g [B, L, H]`` float32 log-decays, ``<= 0``;
    ``beta [B, L, H]`` float32 in [0, 1]. Returns ``o [B, L, H, V]`` in
    ``v.dtype``.
    """
    _, length, heads = g.shape
    row_bytes = CHUNK_TENSORS * 4 * heads * chunk * (length + (-length) % chunk)
    return rows_in_groups(lambda *row: _delta_rule_of_rows(*row, chunk), (q, k, v, g, beta), row_bytes, remat=True)


def _delta_rule_of_rows(q, k, v, g, beta, chunk: int):
    """`gated_delta_rule` for rows whose chunks' tensors all stand at once."""
    with step_scope("gdn_scan"):
        batch, length, heads, kd = k.shape
        vd = v.shape[-1]
        dtype = v.dtype
        pad = (-length) % chunk
        if pad:
            widths = lambda t: [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)
            q, k, v, g, beta = (jnp.pad(t, widths(t)) for t in (q, k, v, g, beta))
        nc = (length + pad) // chunk
        # [B, L, H, ...] -> [C, B, H, Q, ...]: the chunks lead, for the scan over them
        chunks = lambda t: jnp.moveaxis(t.reshape(batch, nc, chunk, heads, *t.shape[3:]), (1, 3), (0, 2))
        q, k, v = chunks(q.astype(dtype)), chunks(k.astype(dtype)), chunks(v)
        beta = chunks(beta.astype(_F32))                            # [C, B, H, Q]
        cum = jnp.cumsum(chunks(g.astype(_F32)), axis=-1)           # log γ, inclusive, within the chunk

        seg = cum[..., :, None] - cum[..., None, :]                 # log(γ_t / γ_s) at [t, s]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))            # 0 above the diagonal
        kk = jnp.einsum("cbhtk,cbhsk->cbhts", k, k, preferred_element_type=_F32)
        a = jnp.where(jnp.tril(lower, -1), kk * decay * beta[..., None], 0.0)
        t_inv = unit_lower_inverse(a).astype(dtype)                 # [C, B, H, Q, Q]

        gamma = jnp.exp(cum)
        v_beta = (v.astype(_F32) * beta[..., None]).astype(dtype)
        k_beta_gamma = (k.astype(_F32) * (beta * gamma)[..., None]).astype(dtype)
        own = jnp.einsum("cbhts,cbhsv->cbhtv", t_inv, v_beta, preferred_element_type=_F32)         # T(βV)
        reads = jnp.einsum("cbhts,cbhsk->cbhtk", t_inv, k_beta_gamma, preferred_element_type=_F32).astype(dtype)  # T(βγK)
        qk = jnp.einsum("cbhtk,cbhsk->cbhts", q, k, preferred_element_type=_F32)
        within = (qk * decay).astype(dtype)                         # s <= t, the diagonal kept
        q_gamma = (q.astype(_F32) * gamma[..., None]).astype(dtype)
        k_to_end = (k.astype(_F32) * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)
        gamma_end = jnp.exp(cum[..., -1])                           # [C, B, H]

        def one_chunk(state, per_chunk):
            own_c, reads_c, within_c, q_gamma_c, k_to_end_c, gamma_end_c = per_chunk
            entering = state.astype(dtype)
            u = (own_c - jnp.einsum("bhtk,bhkv->bhtv", reads_c, entering, preferred_element_type=_F32)).astype(dtype)
            out = (jnp.einsum("bhtk,bhkv->bhtv", q_gamma_c, entering, preferred_element_type=_F32)
                   + jnp.einsum("bhts,bhsv->bhtv", within_c, u, preferred_element_type=_F32))
            state = (gamma_end_c[..., None, None] * state
                     + jnp.einsum("bhsk,bhsv->bhkv", k_to_end_c, u, preferred_element_type=_F32))
            return state, out.astype(dtype)

        zeros = jnp.zeros((batch, heads, kd, vd), _F32)
        _, out = lax.scan(one_chunk, zeros, (own, reads, within, q_gamma, k_to_end, gamma_end))
        out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(batch, nc * chunk, heads, vd)  # [C,B,H,Q,V] -> [B,L,H,V]
        return out[:, :length]
