"""The control's precisions: the same mathematics with every convolution and matrix product
rounded below what the configuration states. Never the reference itself (``f32``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "fp8")


def round_to(x, precision: str, fp8=None):
    """Round values to the control's precision and back (the control only)."""
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(fp8).max)
    return (x / scale).astype(fp8).astype(jnp.float32) * scale


def product(f, a, b, precision: str):
    """``f(a, b)`` for a convolution or matrix product ``f``, in the control's precision.

    ``f32`` is the reference: the operands as they are. ``bf16`` rounds both
    operands. ``fp8`` is the recipe an fp8 training step uses: both operands in
    e4m3 and, on the way back, the incoming gradient in e5m2, each with a
    per-tensor scale; sums stay in float32.
    """
    if precision == "f32":
        return f(a, b)

    @jax.custom_vjp
    def rounded(a, b):
        return f(round_to(a, precision, jnp.float8_e4m3fn), round_to(b, precision, jnp.float8_e4m3fn))

    def fwd(a, b):
        y, vjp = jax.vjp(f, round_to(a, precision, jnp.float8_e4m3fn), round_to(b, precision, jnp.float8_e4m3fn))
        return y, vjp

    def bwd(vjp, dy):
        return vjp(round_to(dy, precision, jnp.float8_e5m2) if precision == "fp8" else dy)

    rounded.defvjp(fwd, bwd)
    return rounded(a, b)
