"""State-space scan: Mamba-2's selective recurrence, computed chunk by chunk.

Per head, with the decay ``a_t = exp(Δ_t·A)`` (``A < 0``) and its group's
``B_t``, ``C_t`` (width N)::

    S_t = a_t · S_{t-1} + Δ_t · x_t ⊗ B_t        (S in R^{P x N})
    y_t = S_t · C_t + D · x_t

`ssd_scan` is the chunked form (Dao & Gu 2024, "state-space duality"): inside
a chunk of Q steps the recurrence unrolls into two matrix products,
``(C·Bᵀ ⊙ decay) · (Δ·x)``, which the MXU runs; one state a chunk is carried by
a `lax.scan` over the chunks (the only sequential part, L/Q steps); the state
entering a chunk is read out through ``C`` with the decay since the chunk's
start. The backward pass is autodiff's of exactly this. Log-decays, their
cumulative sums, every ``exp`` and the carried state are float32 whatever the
compute dtype; the products take operands of ``x.dtype`` and accumulate in
float32. A length that is no multiple of the chunk is padded with steps of
``Δ = 0`` (decay 1, no input), which change no state, and the padding's
outputs are dropped.

``tests/test_nemotron_h.py`` holds the same mathematics as a `lax.scan` over
time, which the chunked form is tested against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from distribuuuu_tpu.obs.trace import step_scope


def _grouped(t, groups: int):
    """``[B, L, H, ...] -> [B, L, G, H/G, ...]``: a group's heads side by side."""
    b, l, h = t.shape[:3]
    return t.reshape(b, l, groups, h // groups, *t.shape[3:])


def ssd_scan(x, dt, a, b, c, d_skip, chunk: int):
    """Chunked selective scan.

    ``x [B, L, H, P]``; ``dt [B, L, H]`` float32, positive (after softplus);
    ``a [H]`` float32, negative; ``b, c [B, L, G, N]`` with ``H % G == 0``
    (heads ``g·H/G … (g+1)·H/G - 1`` read group ``g``); ``d_skip [H]``.
    Returns ``y [B, L, H, P]`` in ``x.dtype``.
    """
    with step_scope("ssm_scan"):
        batch, length, heads, p = x.shape
        groups, n = b.shape[2], b.shape[3]
        dtype, f32 = x.dtype, jnp.float32
        pad = (-length) % chunk
        if pad:
            widths = lambda t: [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)
            x, dt, b, c = (jnp.pad(t, widths(t)) for t in (x, dt, b, c))
        nc = (length + pad) // chunk
        chunks = lambda t: t.reshape(batch, nc, chunk, *t.shape[2:])

        dt = dt.astype(f32)
        log_a = chunks(_grouped(dt * a.astype(f32), groups))        # [B, C, Q, G, R], <= 0
        cum = jnp.cumsum(log_a, axis=2)                            # inclusive, within the chunk
        xdt = chunks(_grouped((x.astype(f32) * dt[..., None]).astype(dtype), groups))  # [B, C, Q, G, R, P]
        bc, cc = chunks(b), chunks(c)                              # [B, C, Q, G, N]

        # inside a chunk: y_l += sum_{s<=l} (C_l·B_s) exp(cum_l - cum_s) Δ_s x_s
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc, preferred_element_type=f32)
        seg = cum[:, :, :, None] - cum[:, :, None]                 # [B, C, l, s, G, R]
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))          # 0 above the diagonal
        weights = (cb[..., None] * decay.transpose(0, 1, 4, 2, 3, 5)).astype(dtype)  # [B, C, G, l, s, R]
        y = jnp.einsum("bcglsr,bcsgrp->bclgrp", weights, xdt, preferred_element_type=f32)

        # each chunk's own contribution to the state at its end
        to_end = jnp.exp(cum[:, :, -1:] - cum)                     # [B, C, Q, G, R]
        own = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bc,
                         (xdt.astype(f32) * to_end[..., None]).astype(dtype), preferred_element_type=f32)

        # the recurrence over chunk states, float32: S_in[c+1] = exp(sum of chunk c's log-decays) S_in[c] + own[c]
        chunk_decay = jnp.exp(cum[:, :, -1])                       # [B, C, G, R]

        def carry_state(state, per_chunk):
            decay_c, own_c = per_chunk
            return decay_c[..., None, None] * state + own_c, state

        zeros = jnp.zeros(own.shape[:1] + own.shape[2:], f32)
        _, entering = lax.scan(carry_state, zeros,
                               (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(own, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)                    # [B, C, G, R, P, N]

        # read-out of the entering state: y_l += exp(cum_l) C_l · S_in
        y_in = jnp.einsum("bclgn,bcgrpn->bclgrp", cc, entering.astype(dtype), preferred_element_type=f32)
        y = y + y_in * jnp.exp(cum)[..., None]

        y = y.reshape(batch, nc * chunk, heads, p)[:, :length]
        y = y + d_skip.astype(f32)[:, None] * x[:, :length].astype(f32)
        return y.astype(dtype)
