"""Fused MoE dispatch/combine kernels (Pallas interpreter) vs the einsum path.

The oracle-equality pattern every kernel in this repo follows: the fused
path must match the einsum formulation exactly — forward, gradients, the
routing metadata, and the drop-at-capacity boundary — before any hardware
verdict is even interesting (tests/test_chip_compile.py holds the compiler's
refusal of both kernels).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from distribuuuu_tpu.ops import moe_kernel
from distribuuuu_tpu.ops.moe_kernel import (
    fused_moe_combine,
    fused_moe_dispatch,
    oracle_combine,
    oracle_dispatch,
)
from distribuuuu_tpu.parallel import switch_moe
from distribuuuu_tpu.runtime import create_mesh

D, E = 8, 8


def _inputs(n, d=D, e=E, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)) * scale, jnp.float32)
    gate = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    return x, gate


@pytest.mark.parametrize("n,capacity,block_n", [(37, 3, 16), (64, 2, 64), (8, 1, 128)])
def test_dispatch_matches_oracle(n, capacity, block_n):
    """send buffer, routing metadata and aux sums — incl. a ragged last tile
    (n % block_n != 0) and a single-tile grid (block_n > n)."""
    x, gate = _inputs(n)
    got = fused_moe_dispatch(
        x, gate, capacity=capacity, block_n=block_n, interpret=True
    )
    want = oracle_dispatch(x, gate, capacity)
    send, top, pos, w, fp = (np.asarray(a) for a in got)
    osend, otop, opos, ow, ofp = (np.asarray(a) for a in want)
    np.testing.assert_allclose(send, osend, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(top, otop)
    np.testing.assert_array_equal(pos, opos)
    np.testing.assert_allclose(w, ow, rtol=1e-6, atol=0)
    np.testing.assert_allclose(fp, ofp, rtol=1e-6, atol=1e-6)


def test_combine_matches_oracle_and_drops_to_zero():
    n, capacity = 29, 2
    x, gate = _inputs(n, seed=3)
    send, top, pos, w, _ = fused_moe_dispatch(
        x, gate, capacity=capacity, block_n=16, interpret=True
    )
    rng = np.random.default_rng(4)
    back = jnp.asarray(rng.standard_normal((E, capacity, D)), jnp.float32)
    got = np.asarray(fused_moe_combine(back, top, pos, w, block_n=16, interpret=True))
    want = np.asarray(oracle_combine(back, top, pos, w))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # dropped tokens (w == 0) combine to EXACT zeros — the Switch residual
    # contract the einsum path guarantees
    dropped = np.asarray(w) == 0.0
    assert dropped.any(), "no overflow at this capacity — dead test"
    np.testing.assert_array_equal(got[dropped], 0.0)


def test_grads_match_oracle_through_expert():
    """d/d{x, gate, expert-side} through dispatch → stand-in expert →
    combine + aux: the custom-VJP recompute backward must transpose exactly
    like autodiff through the einsum formulation."""
    n, capacity = 37, 3
    x, gate = _inputs(n, seed=5)
    rng = np.random.default_rng(6)
    b0 = jnp.asarray(rng.standard_normal((E, capacity, D)), jnp.float32)

    def make_loss(dispatch, combine):
        def f(x_, g_, b_):
            send, top, pos, w, fp = dispatch(x_, g_)
            out = combine(jnp.tanh(send) + b_, top, pos, w)
            return jnp.sum(out**2) + 0.01 * jnp.sum(fp[0] * fp[1])

        return f

    fused = make_loss(
        lambda x_, g_: fused_moe_dispatch(
            x_, g_, capacity=capacity, block_n=16, interpret=True
        ),
        lambda b_, t_, p_, w_: fused_moe_combine(
            b_, t_, p_, w_, block_n=16, interpret=True
        ),
    )
    oracle = make_loss(
        lambda x_, g_: oracle_dispatch(x_, g_, capacity), oracle_combine
    )
    vf, gf = jax.value_and_grad(fused, argnums=(0, 1, 2))(x, gate, b0)
    vo, go = jax.value_and_grad(oracle, argnums=(0, 1, 2))(x, gate, b0)
    np.testing.assert_allclose(float(vf), float(vo), rtol=1e-6)
    for a, b in zip(gf, go):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def _shard_moe(fused, capacity, x, y_t, params, expert_fn, dtype=jnp.float32):
    """Loss + grads of switch_moe under the expert mesh, either path."""
    mesh = create_mesh({"expert": E})

    def body(gate, experts, x_local, y_local):
        experts = jax.tree.map(lambda a: a[0], experts)
        x_local, y_local = x_local[0], y_local[0]

        def loss_fn(p):
            out, aux = switch_moe(
                x_local.astype(dtype), p["gate"], p["experts"], expert_fn,
                capacity=capacity, axis_name="expert",
                fused=fused, interpret=True,
            )
            return jnp.mean((out.astype(jnp.float32) - y_local) ** 2) + 0.01 * aux

        loss, grads = jax.value_and_grad(loss_fn)(
            {"gate": gate, "experts": experts}
        )
        return (
            lax.pmean(loss, "expert"),
            lax.pmean(grads["gate"], "expert"),
            jax.tree.map(lambda g: g[None] / E, grads["experts"]),
        )

    f = jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P("expert"), P("expert"), P("expert")),
            out_specs=(P(), P(), P("expert")),
            check_vma=False,
        )
    )
    return f(params["gate"], params["experts"], x, y_t)


def _moe_params(key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": 0.7 * jax.random.normal(k1, (D, E), jnp.float32),
        "experts": {
            "w": 0.5 * jax.random.normal(k2, (E, D, 2 * D), jnp.float32),
            "v": 0.5 * jax.random.normal(k3, (E, 2 * D, D), jnp.float32),
        },
    }


def _expert_fn(params, x):
    return jnp.tanh(x @ params["w"]) @ params["v"]


@pytest.mark.parametrize("capacity", [2, 4])
def test_fused_switch_moe_matches_einsum_under_mesh(capacity):
    """The whole switch_moe (gate → dispatch → all_to_all → expert →
    all_to_all → combine → aux), fused vs einsum, fwd + grads."""
    n_local = 6
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((E, n_local, D)), jnp.float32)
    y_t = jnp.asarray(rng.standard_normal((E, n_local, D)), jnp.float32)
    params = _moe_params(jax.random.PRNGKey(1))
    l0, g0, e0 = _shard_moe(False, capacity, x, y_t, params, _expert_fn)
    l1, g1, e1 = _shard_moe(True, capacity, x, y_t, params, _expert_fn)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-5, atol=1e-6)
    for key in ("w", "v"):
        np.testing.assert_allclose(
            np.asarray(e0[key]), np.asarray(e1[key]), rtol=1e-5, atol=1e-6
        )


def test_fused_capacity_boundary_bf16_matches_einsum():
    """The exact overflow boundary under bf16 inputs: every token routed to
    one expert, capacity = n_local - 1, so precisely the LAST local token
    drops. Fused and einsum must agree fwd + grad, and the dropped token
    must come back as exact zeros on both paths — the f32-dispatch contract
    the kernel honors even when the activations are half precision."""
    n_local = 4
    capacity = n_local - 1
    rng = np.random.default_rng(7)
    # positive tokens + a gate with only expert 0's column set: every token's
    # expert-0 logit is positive and the rest are zero, so routing is forced
    # and every shard overflows its capacity by exactly one token
    x = jnp.asarray(np.abs(rng.standard_normal((E, n_local, D))) + 0.1, jnp.float32)
    y_t = jnp.asarray(rng.standard_normal((E, n_local, D)), jnp.float32)
    params = _moe_params(jax.random.PRNGKey(2))
    params["gate"] = jnp.zeros((D, E), jnp.float32).at[:, 0].set(5.0)
    l0, g0, e0 = _shard_moe(
        False, capacity, x, y_t, params, _expert_fn, dtype=jnp.bfloat16
    )
    l1, g1, e1 = _shard_moe(
        True, capacity, x, y_t, params, _expert_fn, dtype=jnp.bfloat16
    )
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-4, atol=1e-5)
    for key in ("w", "v"):
        np.testing.assert_allclose(
            np.asarray(e0[key]), np.asarray(e1[key]), rtol=1e-4, atol=1e-5
        )

    # and the dropped token's combined output is exactly zero on both paths
    mesh = create_mesh({"expert": E})

    def fwd(fused):
        def body(experts, x_local):
            out, _ = switch_moe(
                x_local[0].astype(jnp.bfloat16), params["gate"],
                jax.tree.map(lambda a: a[0], experts), _expert_fn,
                capacity=capacity, axis_name="expert",
                fused=fused, interpret=True,
            )
            return out[None]

        jf = jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=(P("expert"), P("expert")),
                out_specs=P("expert"), check_vma=False,
            )
        )
        return jf(params["experts"], x)

    for fused in (False, True):
        out = np.asarray(fwd(fused), np.float32)
        assert np.abs(out[:, :capacity]).max() > 1e-3
        np.testing.assert_array_equal(out[:, capacity:], 0.0)


def test_vmem_budget_guard_falls_back_to_einsum(monkeypatch):
    """Shapes whose [E, C, D] buffer exceeds the VMEM budget fall back to
    the einsum formulation (identical numbers, one warning, counter bumped)
    instead of failing opaquely inside Mosaic on chip."""
    monkeypatch.setenv("DTPU_MOE_VMEM_BUDGET_MB", "0.001")
    n, capacity = 16, 2
    x, gate = _inputs(n, seed=11)
    before = moe_kernel._VMEM_GUARD.fallbacks
    got = fused_moe_dispatch(x, gate, capacity=capacity, interpret=True)
    assert moe_kernel._VMEM_GUARD.fallbacks == before + 1, "dispatch guard never fired"
    want = oracle_dispatch(x, gate, capacity)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    send, top, pos, w, _ = got
    back = jnp.asarray(
        np.random.default_rng(12).standard_normal((E, capacity, D)), jnp.float32
    )
    before = moe_kernel._VMEM_GUARD.fallbacks
    out = fused_moe_combine(back, top, pos, w, interpret=True)
    assert moe_kernel._VMEM_GUARD.fallbacks == before + 1, "combine guard never fired"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(oracle_combine(back, top, pos, w)), rtol=1e-6
    )
    # the fallback stays differentiable (it IS the einsum formulation)
    g = jax.grad(
        lambda x_: jnp.sum(
            fused_moe_dispatch(x_, gate, capacity=capacity, interpret=True)[0] ** 2
        )
    )(x)
    assert bool(jnp.all(jnp.isfinite(g)))

    # normal shapes stay on the kernel
    monkeypatch.delenv("DTPU_MOE_VMEM_BUDGET_MB")
    before = moe_kernel._VMEM_GUARD.fallbacks
    fused_moe_dispatch(x, gate, capacity=capacity, interpret=True)
    assert moe_kernel._VMEM_GUARD.fallbacks == before
