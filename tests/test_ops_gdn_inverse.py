"""The kernel pair behind `ops.gdn.unit_lower_inverse` (`ops/gdn_inverse.py`) against the XLA body it
stands in for and against float64, under the Pallas interpreter asked for explicitly: the inverse, its
gradient, the delta rule through it against the recurrence a position at a time, and the choice between
the two realisations with its counters. CPU, small tile counts."""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distribuuuu_tpu.obs.monitors import MonitoringBridge
from distribuuuu_tpu.ops import gdn, gdn_inverse

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location("reference_qwen3_next_for_the_inverse",
                                               os.path.join(HERE, "reference", "qwen3_next.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

V5E = "TPU v5 lite"


def tiles_as_the_cell_draws_them(n: int, q: int, scale: float, seed: int = 0):
    """``β_t (γ_t / γ_s)(k_t · k_s)`` below the diagonal: unit keys, decays and write strengths in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 3)
    k = jax.random.normal(ks[0], (n, q, 8))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    cum = jnp.cumsum(-jnp.exp(jax.random.normal(ks[1], (n, q)) - 2.0), axis=-1)
    decay = jnp.exp(cum[:, :, None] - cum[:, None, :])
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (n, q, 1)))
    return jnp.tril(scale * beta * decay * jnp.einsum("ntk,nsk->nts", k, k), -1)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


def through_the_kernels(monkeypatch):
    """`unit_lower_inverse` as a TPU mesh would trace it, the kernels under the interpreter conftest asks for."""
    monkeypatch.setattr(gdn, "_takes_the_kernels", lambda a: True)


# -- the forward kernel -----------------------------------------------------------------------------------

@pytest.mark.parametrize("scale", [1.0, 10.0], ids=["as_the_cell", "ten_times"])
@pytest.mark.parametrize("tiles", [2 * gdn_inverse.TILES, gdn_inverse.TILES + 5], ids=["whole_blocks", "a_part_block"])
@pytest.mark.parametrize("q", [16, 64])
def test_the_kernel_inverts_as_float64_does_and_as_the_squarings_do(q, tiles, scale):
    a = tiles_as_the_cell_draws_them(tiles, q, scale)
    assert float(jnp.abs(a).max()) < scale
    got = gdn_inverse.inverse(a, interpret=True)
    want = np.linalg.inv(np.eye(q) + np.asarray(a, np.float64))
    squarings = gdn._inverse(a, False, False)
    assert got.shape == a.shape and got.dtype == jnp.float32
    assert rel(got, squarings) <= 2e-6
    # ten times the cell's entries make an inverse of entries up to 700 at 16 columns, where float32 still
    # holds, and up to 1e7 at 64, where no float32 product chain does: there the kernel is held to the squarings
    assert rel(squarings, want) <= 2e-6 or np.abs(want).max() > 1e6
    assert rel(got, want) <= max(2e-6, 1.5 * rel(squarings, want))
    np.testing.assert_array_equal(np.triu(np.asarray(got), 1), 0.0)
    np.testing.assert_array_equal(np.diagonal(np.asarray(got), axis1=-2, axis2=-1), 1.0)


# -- the backward kernel, and the gradient through `unit_lower_inverse` ------------------------------------

@pytest.mark.parametrize("tiles", [gdn_inverse.TILES, 3], ids=["a_block", "under_a_block"])
@pytest.mark.parametrize("q", [16, 64])
def test_the_backward_kernel_is_the_inverses_own_gradient(q, tiles):
    a = tiles_as_the_cell_draws_them(tiles, q, 1.0, seed=1)
    d_inverse = jax.random.normal(jax.random.key(2), a.shape)
    inverse = np.linalg.inv(np.eye(q) + np.asarray(a, np.float64))
    transposed = np.swapaxes(inverse, -1, -2)
    want = -transposed @ np.asarray(d_inverse, np.float64) @ transposed
    got = gdn_inverse.inverse_bwd(jnp.asarray(inverse, jnp.float32), d_inverse, interpret=True)
    xla, = gdn._inverse_bwd(False, False, jnp.asarray(inverse, jnp.float32), d_inverse)
    assert rel(got, want) <= 2e-6 and rel(got, xla) <= 2e-6


@pytest.mark.parametrize("lead", [(5,), (3, 2, 2)], ids=["flat", "chunks_rows_heads"])
@pytest.mark.parametrize("q", [16, 64])
def test_the_gradient_through_the_inverse_is_the_same_by_either_realisation(q, lead, monkeypatch):
    """`unit_lower_inverse` with the leading axes the delta rule hands it, flattened around the calls."""
    tiles = int(np.prod(lead))
    a = tiles_as_the_cell_draws_them(tiles, q, 1.0, seed=3).reshape(*lead, q, q)
    weight = jax.random.normal(jax.random.key(4), a.shape)
    loss = lambda a: jnp.sum(weight * gdn.unit_lower_inverse(a))
    value_xla, grad_xla = jax.value_and_grad(loss)(a)
    through_the_kernels(monkeypatch)
    value, grad = jax.jit(jax.value_and_grad(loss))(a)
    assert grad.shape == a.shape
    np.testing.assert_allclose(value, value_xla, rtol=1e-5)
    assert rel(grad, grad_xla) <= 2e-6
    # and the derivative of the inverse along a direction, by differences in float64
    direction = np.tril(np.asarray(jax.random.normal(jax.random.key(5), a.shape), np.float64), -1)
    inv64 = lambda x: np.linalg.inv(np.eye(q) + x)
    a64, h = np.asarray(a, np.float64), 1e-6
    want = np.sum(np.asarray(weight, np.float64) * (inv64(a64 + h * direction) - inv64(a64 - h * direction))) / (2 * h)
    np.testing.assert_allclose(np.sum(np.asarray(grad, np.float64) * direction), want, rtol=1e-4)


# -- the delta rule through the kernels against the recurrence a position at a time ------------------------

def _rule_inputs(length: int, b=2, h=3, kd=8, vd=6):
    ks = jax.random.split(jax.random.key(0), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, length, h, kd))) * kd ** -0.5
    k = unit(jax.random.normal(ks[1], (b, length, h, kd)))
    v = jax.random.normal(ks[2], (b, length, h, vd))
    g = -jnp.exp(2.0 * jax.random.normal(ks[3], (b, length, h)))
    beta = jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (b, length, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("length", [128, 72], ids=["whole_chunks", "not_a_multiple"])
def test_the_delta_rule_through_the_kernels_matches_the_recurrence_values_and_gradients(length, chunk, monkeypatch):
    through_the_kernels(monkeypatch)
    bridge = MonitoringBridge().install()
    try:
        inputs = _rule_inputs(length)
        chunked = lambda q, k, v, g, beta: gdn.gated_delta_rule(q, k, v, g, beta, chunk=chunk)
        plain = lambda q, k, v, g, beta: ref.delta_rule(q, k, v, jnp.exp(g), beta)
        np.testing.assert_allclose(chunked(*inputs), plain(*inputs), rtol=1e-4, atol=1e-5)  # float32, chunks of up to 64
        grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3, 4)))(*inputs)
        got, want = grads(chunked), grads(plain)
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(chunked(*a)), argnums=(0, 1, 2, 3, 4)))(*inputs))
    finally:
        bridge.close()
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6)) <= 2e-4, name
    assert gdn.KERNEL_CALLS_EVENT not in bridge.snapshot()["counters"]  # the choice was the test's: nothing counted
    assert "dtpu_gdn_inverse" in text and "dtpu_gdn_inverse_bwd" in text


# -- the choice, from what the trace can observe, and its counters -----------------------------------------

@pytest.mark.parametrize("kind, q, dtype, fits", [
    (V5E, 64, jnp.float32, True),
    (V5E, 16, jnp.float32, True),
    ("TPU v4", 128, jnp.float32, True),
    ("cpu", 64, jnp.float32, False),           # a CPU mesh: XLA's squarings
    (V5E, 64, jnp.bfloat16, False),            # the inverse is float32's
    (V5E, 20, jnp.float32, False),             # no whole number of sublane tiles
    (V5E, 4, jnp.float32, False),
    (V5E, 256, jnp.float32, False),            # wider than a step's buffers are sized for
])
def test_the_pair_takes_float32_tiles_of_whole_sublane_groups_on_tpus(kind, q, dtype, fits):
    assert gdn_inverse.inverse_fits(kind, q, dtype) is fits


def _counted(fn, *args):
    bridge = MonitoringBridge().install()
    try:
        text = str(jax.make_jaxpr(fn)(*args))  # the interpreter leaves no kernel in a lowered text: the trace names it
    finally:
        bridge.close()
    counters = bridge.snapshot()["counters"]
    return text, {k: counters.get(k, 0) for k in (gdn.KERNEL_CALLS_EVENT, gdn.XLA_CALLS_EVENT)}


def test_outside_any_mesh_the_squarings_are_xlas_and_nothing_is_counted():
    a = tiles_as_the_cell_draws_them(4, 64, 1.0)
    text, counters = _counted(gdn.unit_lower_inverse, a)
    assert "dtpu_gdn_inverse" not in text and counters == {gdn.KERNEL_CALLS_EVENT: 0, gdn.XLA_CALLS_EVENT: 0}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_inside_a_cpu_mesh_the_squarings_are_xlas_and_counted_once_a_traced_call(grad):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    a = tiles_as_the_cell_draws_them(4, 64, 1.0)
    fn = jax.grad(lambda a: jnp.sum(gdn.unit_lower_inverse(a))) if grad else gdn.unit_lower_inverse
    fn = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    text, counters = _counted(fn, a)
    assert "dtpu_gdn_inverse" not in text
    assert counters == {gdn.KERNEL_CALLS_EVENT: 0, gdn.XLA_CALLS_EVENT: 1}


@pytest.mark.parametrize("q, kernels", [(64, True), (16, True), (20, False)])
def test_a_mesh_of_tpus_takes_the_kernels_where_the_tile_fits_and_counts_either_way(q, kernels, monkeypatch):
    """The described chip's own mesh is `tests/test_chip_compile.py`'s; here the mesh says it holds TPUs."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fits = gdn_inverse.inverse_fits
    monkeypatch.setattr(gdn_inverse, "inverse_fits", lambda kind, q, dtype: fits(V5E, q, dtype))
    a = tiles_as_the_cell_draws_them(4, q, 1.0)
    fn = jax.shard_map(gdn.unit_lower_inverse, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    text, counters = _counted(fn, a)
    assert ("dtpu_gdn_inverse" in text) is kernels
    assert counters == {gdn.KERNEL_CALLS_EVENT: int(kernels), gdn.XLA_CALLS_EVENT: int(not kernels)}
    if kernels:  # and the values, under the interpreter that conftest asked for
        want = np.linalg.inv(np.eye(q) + np.asarray(a, np.float64))
        assert rel(jax.jit(fn)(a), want) <= 2e-6
