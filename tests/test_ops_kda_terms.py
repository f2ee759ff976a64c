"""The kernel pair behind the Kimi delta rule's within-chunk products (`ops/kda_terms.py`) against the XLA body
it stands in for, under the Pallas interpreter asked for explicitly: a chunk's terms and their gradients by
either realisation, the rule through the kernels against the recurrence a position at a time, and the choice
between the two realisations with its counters. CPU, small tile counts."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distribuuuu_tpu.obs.monitors import MonitoringBridge
from distribuuuu_tpu.ops import kda, kda_terms

from test_bailing_hybrid import recurrence, rel

V5E = "TPU v5 lite"
BOUND = -5.0


def through_the_kernels(monkeypatch):
    """The products as a TPU mesh would trace them, the kernels under the interpreter conftest asks for."""
    monkeypatch.setattr(kda, "_takes_the_kernels", lambda k: True)


def chunk_inputs(case: str, n=3, h=2, chunk=64, kd=128, vd=8, seed=0):
    """A chunk's ``q, k, v, g, β [N, H, Q, ·]`` as `ops.kda.kimi_delta_rule` hands them to `_chunk_terms`."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (n, h, chunk, kd))) * kd ** -0.5
    k = unit(jax.random.normal(ks[1], (n, h, chunk, kd)))
    v = jax.random.normal(ks[2], (n, h, chunk, vd))
    g = BOUND * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (n, h, chunk, kd)))  # channels from ~0 to ~bound
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (n, h, chunk)))
    position = jnp.arange(chunk)[:, None]
    if case == "a_sub_chunk_at_the_bound":  # every channel of sub-chunk 1 forgets all but e^-5 a step: e^75 across it
        g = jnp.where((position >= 16) & (position < 32), BOUND * (1 - 1e-6), g)
    elif case == "rows_that_write_nothing":
        beta = jnp.where(jax.random.uniform(ks[5], beta.shape) < 0.3, 0.0, beta)
    elif case == "a_ragged_end":  # the last chunk of a length of 64·n − 23: its padding writes and decays nothing
        pad = (jnp.arange(n)[:, None, None] == n - 1) & (jnp.arange(chunk)[None, None, :] >= chunk - 23)
        q, k, v, g = (jnp.where(pad[..., None], 0.0, t) for t in (q, k, v, g))
        beta = jnp.where(pad, 0.0, beta)
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("case, chunk, tol", [
    ("spread", 64, 1e-5),
    # products of factors up to e^75 and down to e^-75: the two realisations' sums round apart by more
    ("a_sub_chunk_at_the_bound", 64, 1e-4),
    ("rows_that_write_nothing", 64, 1e-5),
    ("a_ragged_end", 64, 1e-5),
    ("the_longest_chunk", 128, 1e-5),
    ("bfloat16", 64, 8e-3),  # the compute dtype's: the two realisations round a key's gradient at different sums
])
def test_the_kernels_give_the_chunk_terms_and_gradients_that_xla_gives(case, chunk, tol, monkeypatch):
    """`ops.kda._chunk_terms` by the kernels against its XLA body: the six terms the scan reads, and the gradients
    of ``q``, ``k``, ``v``, ``g`` and ``β`` through a loss of all six."""
    inputs = chunk_inputs(case, chunk=chunk)
    weights = [jax.random.normal(jax.random.key(10 + i), t.shape)
               for i, t in enumerate(jax.eval_shape(kda._chunk_terms, *inputs))]
    loss = lambda *a: sum(jnp.sum(w * t.astype(jnp.float32)) for w, t in zip(weights, kda._chunk_terms(*a)))
    both = lambda: (jax.jit(kda._chunk_terms)(*inputs), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*inputs))
    traced = lambda: str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*inputs))
    want, want_grads = both()
    assert kda_terms.FWD_NAME not in traced()
    through_the_kernels(monkeypatch)
    got, got_grads = both()
    assert kda_terms.FWD_NAME in traced() and kda_terms.BWD_NAME in traced()
    for name, a, b in zip(("own", "reads", "within", "q_gamma", "k_to_end", "gamma_end"), got, want):
        assert a.dtype == b.dtype and bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        assert rel(a, b, floor=1e-6) <= max(tol, 1e-5), name
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got_grads, want_grads):
        assert a.dtype == b.dtype and bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        assert rel(a, b, floor=1e-6) <= tol, name
    p, w = kda.within_chunk_products(*inputs[:2], jnp.cumsum(inputs[3], axis=-2))
    s = np.arange(chunk)
    np.testing.assert_array_equal(np.asarray(p)[..., s[None, :] >= 16 * (s[:, None] // 16 + 1)], 0.0)
    np.testing.assert_array_equal(np.asarray(w, np.float32)[..., s[None, :] > s[:, None]], 0.0)


@pytest.mark.parametrize("length", [200, 64], ids=["chunks_and_a_ragged_end", "one_chunk"])
def test_the_rule_through_the_kernels_matches_the_recurrence_values_and_gradients(length, monkeypatch):
    from test_bailing_hybrid import _rule_inputs

    through_the_kernels(monkeypatch)
    args = _rule_inputs(length, "at_the_bound", kd=128)
    rule = jax.jit(lambda *a: kda.kimi_delta_rule(*a, chunk=64))
    got, want = rule(*args), recurrence(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    loss = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3, 4)))
    for name, a, b in zip(("q", "k", "v", "g", "beta"), loss(rule)(*args), loss(recurrence)(*args)):
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, b, floor=1e-6) <= 2e-5, name
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda.kimi_delta_rule(*a, chunk=64)),
                                       argnums=(0, 1, 2, 3, 4)))(*args))
    assert kda_terms.FWD_NAME in text and kda_terms.BWD_NAME in text


# -- the choice, from what the trace can observe, and its counters -----------------------------------------

@pytest.mark.parametrize("kind, q, k, fits", [
    (V5E, 64, 128, True),
    (V5E, 16, 256, True),
    ("TPU v4", 128, 128, True),
    ("cpu", 64, 128, False),             # a CPU mesh: XLA's products
    (V5E, 40, 128, False),               # no whole number of sub-chunks
    (V5E, 256, 128, False),              # longer than a step's values are sized for
    (V5E, 64, 64, False),                # keys of no whole lane group
])
def test_the_pair_takes_whole_sub_chunks_and_lane_groups_on_tpus(kind, q, k, fits):
    assert kda_terms.fits(kind, q, k) is fits


def _counted(fn, *args):
    bridge = MonitoringBridge().install()
    try:
        text = str(jax.make_jaxpr(fn)(*args))  # the interpreter leaves no kernel in a lowered text: the trace names it
    finally:
        bridge.close()
    counters = bridge.snapshot()["counters"]
    return text, {name: counters.get(name, 0) for name in (kda.KERNEL_CALLS_EVENT, kda.XLA_CALLS_EVENT)}


def _products_of(chunk: int, kd: int = 128):
    q, k, _, g, _ = chunk_inputs("spread", n=2, chunk=chunk, kd=kd)
    return q, k, jnp.cumsum(g, axis=-2)


def test_outside_any_mesh_the_products_are_xlas_and_nothing_is_counted():
    text, counters = _counted(kda.within_chunk_products, *_products_of(64))
    assert kda_terms.FWD_NAME not in text and counters == {kda.KERNEL_CALLS_EVENT: 0, kda.XLA_CALLS_EVENT: 0}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_inside_a_cpu_mesh_the_products_are_xlas_and_counted_once_a_traced_call(grad):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = kda.within_chunk_products
    if grad:
        fn = jax.grad(lambda q, k, cum: jnp.sum(kda.within_chunk_products(q, k, cum)[0]), argnums=(0, 1, 2))
    fn = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    text, counters = _counted(fn, *_products_of(64))
    assert kda_terms.FWD_NAME not in text
    assert counters == {kda.KERNEL_CALLS_EVENT: 0, kda.XLA_CALLS_EVENT: 1}


@pytest.mark.parametrize("chunk, kd, kernels", [(64, 128, True), (32, 128, True), (64, 64, False)])
def test_a_mesh_of_tpus_takes_the_kernels_where_the_tile_fits_and_counts_either_way(chunk, kd, kernels, monkeypatch):
    """The described chip's own mesh is `tests/test_chip_compile.py`'s; here the mesh says it holds TPUs."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fits = kda_terms.fits
    monkeypatch.setattr(kda_terms, "fits", lambda kind, q, k: fits(V5E, q, k))
    args = _products_of(chunk, kd)
    fn = jax.shard_map(kda.within_chunk_products, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    text, counters = _counted(fn, *args)
    assert (kda_terms.FWD_NAME in text) is kernels
    assert counters == {kda.KERNEL_CALLS_EVENT: int(kernels), kda.XLA_CALLS_EVENT: int(not kernels)}
    if kernels:  # and the values, under the interpreter that conftest asked for
        for a, b in zip(jax.jit(fn)(*args), kda._xla_products(*args)):
            assert rel(a, b, floor=1e-6) <= 1e-6
