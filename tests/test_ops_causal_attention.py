"""The causal attention pair (`ops/causal_attention.py`: `dtpu_causal_attn_fwd`, `dtpu_causal_attn_bwd`)
behind `ops.attention.causal_attention`, against XLA's blocks (`xla_causal_core`), and the route the entry
point takes.

Interpret mode, at a length of 256 in tiles of 128 rows: three tiles on and below the diagonal a head, one
of them off it. What the chip's compiler makes of the pair is `tests/test_chip_compile.py`'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu.obs.monitors import MonitoringBridge
from distribuuuu_tpu.ops import attention, causal_attention
from distribuuuu_tpu.runtime import data_mesh

# (query heads, key heads, own key width, shared key width, value width): latent attention's heads (a shared
# rotary part, values narrower than keys), grouped 8:1 and grouped 4:1
GEOMETRIES = {"latent": (2, 2, 16, 8, 12), "grouped_8": (8, 1, 8, 0, 8), "grouped_4": (4, 1, 16, 0, 16)}
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
LENGTH = 256


@pytest.fixture(autouse=True)
def tiles_of_128(monkeypatch):
    monkeypatch.setattr(causal_attention, "BLOCKS", (128,))


def _inputs(heads, groups, dk, dr, dv, dtype, rows=1, length=LENGTH, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (rows, length, heads, dk + dr))
    k = jax.random.normal(ks[1], (rows, length, groups, dk))
    v = jax.random.normal(ks[2], (rows, length, groups, dv))
    shared = jax.random.normal(ks[3], (rows, length, dr)) if dr else None
    weight = jax.random.normal(ks[4], (rows, length, heads, dv))
    cast = lambda t: None if t is None else t.astype(dtype)
    return (cast(q), cast(k), cast(v), cast(shared)), weight


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))


def _out_and_grads(core, args, weight):
    """The output and the gradient of ``sum(core(*args) · weight)`` for every operand given."""
    given = [a for a in args if a is not None]

    def of_given(*xs):
        it = iter(xs)
        return core(*(None if a is None else next(it) for a in args))

    out, vjp = jax.vjp(of_given, *given)
    return out, vjp(weight.astype(out.dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_kernel_pair_matches_the_xla_blocks(geometry, dtype):
    """Output and every gradient (q, the key heads, the values, the shared key part), each against XLA's blocks
    in the same dtype; a grouped key head's gradient is its query heads' sum. One program for both routes."""
    args, weight = _inputs(*GEOMETRIES[geometry], dtype)
    both = jax.jit(lambda args, weight: [
        _out_and_grads(core, args, weight)
        for core in (lambda *a: attention._flash_causal(*a, True), lambda *a: attention.xla_causal_core(*a, block=128))])
    (out, grads), (want, want_grads) = both(args, weight)
    heads, _, _, _, dv = GEOMETRIES[geometry]
    assert out.shape == (1, LENGTH, heads, dv) and out.dtype == dtype
    assert _rel(out, want) <= TOL[dtype]
    assert len(grads) == len(want_grads) == (4 if args[3] is not None else 3)
    for got, wanted, arg in zip(grads, want_grads, [a for a in args if a is not None]):
        assert got.shape == arg.shape and got.dtype == dtype
        assert _rel(got, wanted) <= 2 * TOL[dtype]


def test_a_row_whose_only_key_is_itself():
    """Row 0 sees key 0 alone: its output is value 0 of its key head exactly, and no gradient reaches its
    query (a softmax over one score does not move with it), whatever the scores of the rows below."""
    args, weight = _inputs(*GEOMETRIES["grouped_4"], jnp.float32)
    out, grads = jax.jit(lambda a, w: _out_and_grads(lambda *x: attention._flash_causal(*x, True), a, w))(args, weight)
    v = args[2]
    np.testing.assert_allclose(out[:, 0], jnp.broadcast_to(v[:, 0], out[:, 0].shape), rtol=1e-6, atol=1e-6)
    assert float(jnp.max(jnp.abs(grads[0][:, 0]))) == 0.0
    assert float(jnp.max(jnp.abs(grads[0][:, 1]))) > 0.0


@pytest.mark.parametrize(
    "kind,l,heads,kv_heads,dk,dr,dv,fuses",
    [
        ("TPU v5 lite", 8192, 32, 32, 128, 64, 128, True),   # kanana2_30b.train: latent attention
        ("TPU v5 lite", 8192, 16, 2, 256, 0, 256, True),     # qwen3_next.train
        ("TPU v5 lite", 8192, 4, 1, 128, 0, 128, True),      # nemotron3_super.train
        ("cpu", 8192, 32, 32, 128, 64, 128, False),          # no TPU: XLA's blocks
        ("TPU v5 lite", 8200, 16, 2, 256, 0, 256, False),    # no tile divides the length
        ("TPU v5 lite", 8192, 4, 1, 96, 0, 96, False),       # widths of no whole lane group
        ("TPU v5 lite", 8192, 32, 32, 128, 192, 128, False),  # a shared part wider than a lane group
        ("TPU v5 lite", 8192, 6, 4, 128, 0, 128, False),     # query heads no whole groups of the key heads
    ],
)
def test_route_follows_device_and_shape(kind, l, heads, kv_heads, dk, dr, dv, fuses):
    assert causal_attention.fits(kind, l, heads, kv_heads, dk, dr, dv, 2) is fuses


def _counted(fn):
    bridge = MonitoringBridge().install()
    try:
        fn()
        return bridge.snapshot()["counters"]
    finally:
        bridge.close()


@pytest.mark.parametrize("fused", [False, True], ids=["cpu_mesh", "kernels"])
def test_entry_counts_the_route_it_takes_inside_a_mesh_and_nothing_outside(fused, monkeypatch):
    """On the CPU mesh the entry takes XLA's blocks and counts them; where the predicate admits the call (as it
    does for TPUs) it takes the pair and counts that. Outside any mesh: XLA's blocks, uncounted."""
    args, _ = _inputs(*GEOMETRIES["latent"], jnp.float32, length=128)
    monkeypatch.setattr(causal_attention, "fits", lambda *a: fused)
    entry = jax.jit(lambda *a: attention.causal_attention(*a))
    outside = _counted(lambda: entry.lower(*args))
    assert attention.CAUSAL_FUSED_EVENT not in outside and attention.CAUSAL_XLA_EVENT not in outside
    traced = []
    with jax.set_mesh(data_mesh(1)):
        counters = _counted(lambda: traced.append(str(jax.make_jaxpr(lambda *a: attention.causal_attention(*a))(*args))))
    assert counters.get(attention.CAUSAL_FUSED_EVENT if fused else attention.CAUSAL_XLA_EVENT) == 1
    assert (attention.CAUSAL_XLA_EVENT if fused else attention.CAUSAL_FUSED_EVENT) not in counters
    assert ("dtpu_causal_attn_fwd" in traced[0]) is fused
