"""The short causal depthwise convolution over time and its `silu`, as one op with its backward pass written out.

For every channel ``c`` and a kernel ``w [K, C]`` (an optional bias ``b [C]``)::

    y[t] = silu(b + Σ_j w[j] · x[t − (K−1) + j])          x[t] = 0 for t < 0

the short convolution a delta-rule mixer (`models/qwen3_next.delta_mixer`) and a
Mamba-2 mixer (`models/nemotron_h.mamba_mixer`) put before their recurrence.

Autodiff of K shifted slices of a padded copy would give each slice a padded
gradient of its own and add the K together, every tap's gradient crossing
HBM. So `jax.custom_vjp` keeps ``x``, ``w`` and ``b`` alone; the
backward pass computes the pre-activation again from ``x``, forms
``g = dy · silu'(pre)`` and then, each as one pass over the same reads::

    dx[t] = Σ_j w[j] · g[t + (K−1) − j]        (zero past the end)
    dw[j] = Σ_{rows, t} g[t] · x[t − (K−1) + j]
    db    = Σ_{rows, t} g[t]

Sums in float32; ``dx`` is cast once to ``x``'s dtype. One algorithm, two
realisations, picked by `_takes_the_kernels` where the call is traced: inside
the trainer's steps on TPUs at shapes `short_conv_kernels.fits` admits, the
kernel pair of `ops/short_conv_kernels.py` (a row's whole length of 128
channels in VMEM a grid step); elsewhere XLA's, whose taps read windows of
``x`` itself (no padded copy of the input is made) and whose ``dx`` reads
windows of ``g``. Forward, recomputed and backward stand under the step scope
``dtpu.short_conv``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops import short_conv_kernels
from distribuuuu_tpu.ops.interpret import pallas_interpret

F32 = jnp.float32
#: `jax.monitoring` events, one a traced `causal_conv_silu` inside a mesh: which realisation it took. The
#: journal's ``counters`` records carry them (obs/monitors.py)
KERNEL_CALLS_EVENT = "short_conv_kernel_calls"
XLA_CALLS_EVENT = "short_conv_xla_calls"


def _takes_the_kernels(x, w, out_dtype) -> bool:
    """The realisation for ``x [B, L, C]``, from what the trace can observe: the kernel pair where a mesh of TPUs
    is in use (the described chips of a compile-only test count as what they describe) and
    `short_conv_kernels.fits` admits the shapes; outside any mesh (``model.init``, shape inference, a test's
    plain call) XLA's, uncounted."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return False
    fits = short_conv_kernels.fits(mesh.abstract_device.device_kind, x.shape[1], x.shape[2], w.shape[0],
                                   x.dtype.itemsize, out_dtype.itemsize)
    jax.monitoring.record_event(KERNEL_CALLS_EVENT if fits else XLA_CALLS_EVENT)
    return fits


def _later(x, s: int):
    """``x`` moved ``s >= 0`` steps later along time (axis 1), zeros before the start: a window of ``x`` itself."""
    if s == 0:
        return x
    zeros = jnp.zeros((x.shape[0], min(s, x.shape[1]), *x.shape[2:]), x.dtype)
    return jnp.concatenate([zeros, x[:, :max(x.shape[1] - s, 0)]], axis=1)


def _earlier(g, s: int):
    """``g`` moved ``s >= 0`` steps earlier along time, zeros past the end; XLA reads it inside the consumer's
    fusion."""
    if s == 0:
        return g
    return lax.pad(g, jnp.zeros((), g.dtype), [(0, 0, 0), (-s, s, 0), (0, 0, 0)])


def _pre(x, w, b):
    """The pre-activation ``b + Σ_j w[j] · x[t − (K−1) + j]`` in float32."""
    k = w.shape[0]
    w = w.astype(F32)
    pre = sum(w[j] * _later(x, k - 1 - j).astype(F32) for j in range(k))
    return pre if b is None else pre + b.astype(F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_silu(x, w, b, out_dtype, kernels: bool, interpret: bool):
    return _conv_silu_fwd(x, w, b, out_dtype, kernels, interpret)[0]


def _conv_silu_fwd(x, w, b, out_dtype, kernels, interpret):
    with step_scope("short_conv"):  # the rules take the scope themselves, as ops/grouped.py's do
        if kernels:
            y = short_conv_kernels.forward(x, w, b, out_dtype, interpret=interpret)
        else:
            y = jax.nn.silu(_pre(x, w, b)).astype(out_dtype)
        return y, (x, w, b)


def _conv_silu_bwd(out_dtype, kernels, interpret, res, dy):
    del out_dtype
    x, w, b = res
    with step_scope("short_conv"):
        k = w.shape[0]
        if kernels:
            dx, sums = short_conv_kernels.backward(x, w, b, dy, interpret=interpret)
            sums = jnp.sum(sums, axis=0)
            dw, db = sums[:k], (None if b is None else sums[k])
        else:
            pre = _pre(x, w, b)
            sig = jax.nn.sigmoid(pre)
            g = dy.astype(F32) * sig * (1.0 + pre * (1.0 - sig))  # silu'(pre) = σ(pre) (1 + pre (1 − σ(pre)))
            w32 = w.astype(F32)
            dx = sum(w32[j] * _earlier(g, k - 1 - j) for j in range(k)).astype(x.dtype)
            dw = jnp.stack([jnp.sum(g * _later(x, k - 1 - j).astype(F32), axis=(0, 1)) for j in range(k)])
            db = None if b is None else jnp.sum(g, axis=(0, 1))
        return dx, dw.astype(w.dtype), None if b is None else db.astype(b.dtype)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv_silu(x, w, b=None, out_dtype=F32):
    """``silu(b + Σ_j w[j] · x[t − (K−1) + j])`` over time for ``x [B, L, C]``, ``w [K, C]``, ``b [C]`` or None:
    float32 sums, the result in ``out_dtype``."""
    out_dtype = jnp.dtype(out_dtype)
    return _conv_silu(x, w, b, out_dtype, _takes_the_kernels(x, w, out_dtype), pallas_interpret())
