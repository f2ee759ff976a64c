"""`ops.short_conv.causal_conv_silu` against the inline formula it replaced in both token mixers (``K`` shifted
slices of a padded copy, then `silu`, autodiff's backward pass): values and the gradients of ``x``, ``w`` and
``b``, on XLA's route and through the kernel pair of `ops/short_conv_kernels.py` under the Pallas interpreter;
the choice between the two with its counters; and the scope and the absent pad in a small model's lowered step.
CPU, small lengths at the cells' channel widths."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distribuuuu_tpu.obs.monitors import MonitoringBridge
from distribuuuu_tpu.ops import short_conv, short_conv_kernels

F32 = jnp.float32
V5E = "TPU v5 lite"


def inline(x, w, b, out_dtype):
    """The mixers' formula before the op: a padded copy, ``K`` shifted slices of it, then `silu`."""
    length, taps = x.shape[1], w.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    pre = sum(w[j] * padded[:, j:j + length] for j in range(taps))
    return jax.nn.silu(pre if b is None else b + pre).astype(out_dtype)


def operands(rows: int, length: int, channels: int, taps: int, bias: bool, dtype=F32, seed: int = 0):
    kx, kw, kb = jax.random.split(jax.random.key(seed), 3)
    bound = taps ** -0.5  # the Mamba convolution's init: a depthwise conv1d's fan-in is its kernel
    x = jax.random.normal(kx, (rows, length, channels), F32).astype(dtype)
    w = jax.random.uniform(kw, (taps, channels), F32, -bound, bound)
    return x, w, (jax.random.uniform(kb, (channels,), F32, -bound, bound) if bias else None)


def values_and_grads(fn, x, w, b, out_dtype):
    """``fn``'s output and the gradients of ``x``, ``w`` (and ``b``) of a loss that weighs every output apart."""
    loss = lambda x, w, b: jnp.sum(jnp.sin(fn(x, w, b, out_dtype).astype(F32)))
    argnums = (0, 1) if b is None else (0, 1, 2)
    return fn(x, w, b, out_dtype), jax.grad(loss, argnums=argnums)(x, w, b)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def assert_matches_the_inline_formula(op, x, w, b, out_dtype):
    got, got_grads = values_and_grads(op, x, w, b, out_dtype)
    want, want_grads = values_and_grads(inline, x, w, b, out_dtype)
    assert got.dtype == want.dtype == jnp.dtype(out_dtype) and got.shape == x.shape
    # a float32 sum in another order; rounded to bfloat16, one unit in the last place of the output
    assert rel(got, want) <= (1e-5 if out_dtype == F32 else 8e-3)
    assert len(got_grads) == len(want_grads)
    for name, g, h in zip(("x", "w", "b"), got_grads, want_grads):
        assert g.dtype == h.dtype and g.shape == h.shape, name
        assert rel(g, h) <= 2e-5, name


# -- XLA's route: every shape the op admits --------------------------------------------------------------

@pytest.mark.parametrize("channels", [8192, 1280], ids=["qwen3_next", "nemotron_h"])
@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("out_dtype", [F32, jnp.bfloat16], ids=["f32_out", "bf16_out"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_xla_route_matches_the_inline_formula_values_and_gradients(bias, out_dtype, taps, channels):
    """Float32 operands, a length (37) that is a multiple of no tile, the two cells' channel widths."""
    x, w, b = operands(2, 37, channels, taps, bias)
    assert_matches_the_inline_formula(short_conv.causal_conv_silu, x, w, b, out_dtype)


def test_a_length_under_the_kernel_width_reads_zeros_before_the_start():
    x, w, b = operands(1, 2, 128, 4, True)
    assert_matches_the_inline_formula(short_conv.causal_conv_silu, x, w, b, F32)


def test_bfloat16_input_gets_a_gradient_summed_in_float32_and_rounded_once():
    """qwen3_next's call: bfloat16 ``x``, float32 out. ``dx`` is one float32 sum cast once, so it lies at least as
    near the float32 gradient as the inline formula's sum of ``K`` bfloat16 terms."""
    x, w, _ = operands(2, 64, 256, 4, False, dtype=jnp.bfloat16)
    exact = jax.grad(lambda x: jnp.sum(jnp.sin(inline(x, w, None, F32))))(x.astype(F32))
    got = jax.grad(lambda x: jnp.sum(jnp.sin(short_conv.causal_conv_silu(x, w, None, F32))))(x)
    old = jax.grad(lambda x: jnp.sum(jnp.sin(inline(x, w, None, F32))))(x)
    assert got.dtype == old.dtype == jnp.bfloat16
    assert np.linalg.norm(np.asarray(got, np.float64) - exact) <= np.linalg.norm(np.asarray(old, np.float64) - exact)
    assert rel(got, exact) <= 8e-3


def test_the_op_keeps_no_float32_activation_for_its_backward_pass():
    """What the backward pass reads is ``x``, ``w`` and ``b``: no ``[B, L, C]`` float32 residual."""
    x, w, b = operands(2, 16, 128, 4, True, dtype=jnp.bfloat16)
    _, residuals = jax.vjp(lambda x, w, b: short_conv.causal_conv_silu(x, w, b, F32), x, w, b)
    leaves = [a for a in jax.tree.leaves(residuals) if hasattr(a, "shape")]
    assert not any(a.shape == x.shape and a.dtype == F32 for a in leaves)


# -- the kernel pair, under the interpreter ---------------------------------------------------------------

def through_the_kernels(x, w, b, out_dtype):
    return short_conv._conv_silu(x, w, b, jnp.dtype(out_dtype), True, True)


@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("case", ["qwen3_next", "nemotron_h"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_the_kernels_match_the_inline_formula_values_and_gradients(bias, case, taps):
    """Two rows of two chunks each (the taps' windows cross a chunk's edge), two lane groups; qwen3_next's dtypes
    (float32 operands here, float32 out) and nemotron_h's (float32 in, bfloat16 out)."""
    x, w, b = operands(2, 2 * short_conv_kernels.ROWS, 256, taps, bias)
    assert_matches_the_inline_formula(through_the_kernels, x, w, b, F32 if case == "qwen3_next" else jnp.bfloat16)


def test_the_kernels_read_and_write_bfloat16_rows():
    """bfloat16 ``x`` as the qwen3_next cell hands it: the kernels' output and gradients are XLA's route's."""
    x, w, _ = operands(1, 2 * short_conv_kernels.ROWS, 128, 4, False, dtype=jnp.bfloat16, seed=3)
    got, got_grads = values_and_grads(through_the_kernels, x, w, None, F32)
    want, want_grads = values_and_grads(lambda *a: short_conv._conv_silu(*a, False, False), x, w, None, jnp.dtype(F32))
    assert rel(got, want) <= 1e-6
    assert rel(got_grads[0], want_grads[0]) <= 8e-3 and rel(got_grads[1], want_grads[1]) <= 1e-5


# -- the choice, from what the trace can observe, and its counters -----------------------------------------

@pytest.mark.parametrize("kind, length, channels, taps, x_bytes, out_bytes, fits", [
    (V5E, 8192, 8192, 4, 2, 4, True),      # qwen3_next.train: bfloat16 in, float32 out
    (V5E, 8192, 1280, 4, 4, 2, True),      # nemotron3_super.train: float32 in, bfloat16 out
    ("TPU v4", 4096, 256, 2, 4, 4, True),
    ("cpu", 8192, 8192, 4, 2, 4, False),   # a CPU mesh: XLA's
    (V5E, 8000, 8192, 4, 2, 4, False),     # a length of no whole chunks
    (V5E, 8192, 1000, 4, 2, 4, False),     # channels of no whole lane groups
    (V5E, 8192, 8192, 18, 2, 4, False),    # more taps than the halo holds
    (V5E, 32768, 8192, 4, 4, 4, False),    # a row's length of 128 channels outgrows a step's VMEM
])
def test_the_pair_takes_whole_chunks_and_lane_groups_on_tpus(kind, length, channels, taps, x_bytes, out_bytes, fits):
    assert short_conv_kernels.fits(kind, length, channels, taps, x_bytes, out_bytes) is fits


def _counted(fn, *args):
    bridge = MonitoringBridge().install()
    try:
        text = str(jax.make_jaxpr(fn)(*args))  # the interpreter leaves no kernel in a lowered text: the trace names it
    finally:
        bridge.close()
    counters = bridge.snapshot()["counters"]
    return text, {k: counters.get(k, 0) for k in (short_conv.KERNEL_CALLS_EVENT, short_conv.XLA_CALLS_EVENT)}


def _conv_grad(x, w):
    return jax.grad(lambda x, w: jnp.sum(short_conv.causal_conv_silu(x, w)), argnums=(0, 1))(x, w)


def test_outside_any_mesh_the_route_is_xlas_and_nothing_is_counted():
    x, w, _ = operands(1, 512, 128, 4, False)
    text, counters = _counted(_conv_grad, x, w)
    assert "dtpu_short_conv" not in text
    assert counters == {short_conv.KERNEL_CALLS_EVENT: 0, short_conv.XLA_CALLS_EVENT: 0}


def test_inside_a_cpu_mesh_the_route_is_xlas_and_counted_once_a_traced_call():
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x, w, _ = operands(1, 512, 128, 4, False)
    fn = jax.shard_map(_conv_grad, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    text, counters = _counted(fn, x, w)
    assert "dtpu_short_conv" not in text
    assert counters == {short_conv.KERNEL_CALLS_EVENT: 0, short_conv.XLA_CALLS_EVENT: 1}


@pytest.mark.parametrize("length, kernels", [(512, True), (500, False)])
def test_a_mesh_of_tpus_takes_the_kernels_where_the_shapes_fit_and_counts_either_way(length, kernels, monkeypatch):
    """The described chip's own mesh is `tests/test_chip_compile.py`'s; here the mesh says it holds TPUs."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fits = short_conv_kernels.fits
    monkeypatch.setattr(short_conv_kernels, "fits", lambda kind, *shape: fits(V5E, *shape))
    x, w, _ = operands(1, length, 128, 4, False)
    fn = jax.shard_map(_conv_grad, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    text, counters = _counted(fn, x, w)
    assert ("dtpu_short_conv_fwd" in text and "dtpu_short_conv_bwd" in text) is kernels
    assert counters == {short_conv.KERNEL_CALLS_EVENT: int(kernels), short_conv.XLA_CALLS_EVENT: int(not kernels)}
    if kernels:  # and the values, under the interpreter that conftest asked for
        want = jax.grad(lambda x, w: jnp.sum(inline(x, w, None, F32)), argnums=(0, 1))(x, w)
        for got, exact in zip(jax.jit(fn)(x, w), want):
            assert rel(got, exact) <= 2e-5
