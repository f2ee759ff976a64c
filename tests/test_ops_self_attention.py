"""The bias-free self-attention pair (`ops/attention.py`: `dtpu_attn_fwd`,
`dtpu_attn_bwd`) against the einsum route, and the route `self_attention`
takes on a CPU mesh.

Interpret mode is asked for here, call by call; nothing infers it. What the
chip's compiler makes of the kernels is `tests/test_chip_compile.py`'s, and
the route for a described TPU is tested there too (one file loads libtpu).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distribuuuu_tpu import trainer
from distribuuuu_tpu.models.vit import ViT
from distribuuuu_tpu.obs.monitors import MonitoringBridge
from distribuuuu_tpu.ops import attention
from distribuuuu_tpu.ops.attention import (
    fused_self_attention,
    self_attention,
    self_attention_fuses,
    xla_self_attention,
)
from distribuuuu_tpu.runtime import data_mesh

# (L, heads, hd): the cell's 197 tokens, MAE's 50 visible ones, a multiple of
# 128, and the two other head widths that tile a 128-lane group
GEOMETRIES = [(197, 2, 64), (50, 4, 64), (256, 2, 64), (40, 4, 32), (24, 1, 128)]
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(l, heads, hd, dtype, scale=1.0, b=2, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(scale * rng.standard_normal((b, l, 3 * heads * hd)), dtype)


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))


def _out_and_grad(fn, qkv, weight):
    def loss(x):
        return jnp.sum(fn(x).astype(jnp.float32) * weight)

    return fn(qkv), jax.grad(loss)(qkv)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("l,heads,hd", GEOMETRIES, ids=lambda v: str(v))
def test_fused_pair_matches_the_einsums(l, heads, hd, dtype):
    qkv = _qkv(l, heads, hd, dtype)
    weight = jnp.asarray(np.random.default_rng(1).standard_normal((2, l, heads * hd)), jnp.float32)
    out, d_qkv = _out_and_grad(lambda x: fused_self_attention(x, heads, True), qkv, weight)
    want, d_want = _out_and_grad(lambda x: xla_self_attention(x, heads), qkv, weight)
    assert out.shape == (2, l, heads * hd) and out.dtype == dtype
    assert d_qkv.shape == qkv.shape and d_qkv.dtype == dtype
    assert _rel(out, want) <= TOL[dtype]
    # every third of the packed gradient on its own: d_q, d_k, d_v
    for part, want_part in zip(jnp.split(d_qkv, 3, axis=-1), jnp.split(d_want, 3, axis=-1)):
        assert _rel(part, want_part) <= TOL[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_large_logits_stay_finite_and_right(dtype):
    """Scores near 8·8·64/8 = 512: exp must see them max-subtracted, forward
    and in the weights the backward recomputes from the saved log-sum-exp."""
    qkv = _qkv(197, 2, 64, dtype, scale=8.0)
    weight = jnp.ones((2, 197, 128), jnp.float32)
    out, d_qkv = _out_and_grad(lambda x: fused_self_attention(x, 2, True), qkv, weight)
    want, d_want = _out_and_grad(lambda x: xla_self_attention(x, 2), qkv, weight)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert bool(jnp.all(jnp.isfinite(d_qkv.astype(jnp.float32))))
    assert _rel(out, want) <= TOL[dtype]
    assert _rel(d_qkv, d_want) <= 2 * TOL[dtype]


def test_padded_keys_carry_no_weight():
    """197 is no multiple of 8 or 128: whatever lies in the tile's padding,
    the result is that of 197 tokens (one more key changes every row)."""
    qkv = _qkv(197, 2, 64, jnp.float32)
    longer = jnp.concatenate([qkv, 50.0 * jnp.ones((2, 1, qkv.shape[-1]), qkv.dtype)], axis=1)
    out = fused_self_attention(qkv, 2, True)
    out_longer = fused_self_attention(longer, 2, True)[:, :197]
    assert _rel(out, xla_self_attention(qkv, 2)) <= TOL[jnp.float32]
    assert _rel(out_longer, out) > 1e-2


@pytest.mark.parametrize(
    "kind,l,heads,hd,fuses",
    [
        ("TPU v5 lite", 197, 12, 64, True),    # vit_b16.train
        ("TPU v5 lite", 50, 12, 64, True),     # MAE's encoder
        ("TPU v5 lite", 197, 16, 32, True),    # MAE's decoder
        ("TPU v5 lite", 197, 16, 64, True),    # vit_l16
        ("cpu", 197, 12, 64, False),           # no TPU: the einsums
        ("TPU v5 lite", 197, 4, 8, False),     # width 32: no whole lane group
        ("TPU v5 lite", 197, 8, 48, False),    # hd 48 does not divide 128
        ("TPU v5 lite", 1025, 12, 64, False),  # a tile too long for VMEM
    ],
)
def test_route_follows_device_and_shape(kind, l, heads, hd, fuses):
    assert self_attention_fuses(kind, l, heads, hd, 2) is fuses


def test_route_reads_nothing_but_device_and_shape(monkeypatch):
    """The biased family's VMEM budget knob does not reach the route."""
    monkeypatch.setenv("DTPU_ATTN_VMEM_BUDGET_MB", "0")
    assert self_attention_fuses("TPU v5 lite", 197, 12, 64, 2) is True


def _counted(fn):
    bridge = MonitoringBridge().install()
    try:
        fn()
        return bridge.snapshot()["counters"]
    finally:
        bridge.close()


def test_outside_a_mesh_the_einsums_run_uncounted():
    qkv = _qkv(12, 2, 64, jnp.float32)
    counters = _counted(lambda: jax.jit(lambda x: self_attention(x, 2)).lower(qkv))
    assert attention.FUSED_CALLS_EVENT not in counters and attention.XLA_CALLS_EVENT not in counters
    np.testing.assert_allclose(
        self_attention(qkv, 2), xla_self_attention(qkv, 2), rtol=1e-6, atol=1e-6
    )


def test_cpu_mesh_takes_the_einsums_and_counts_them(fresh_cfg):
    """The trainer's own step on the CPU mesh, at a geometry the kernels
    would tile: every block takes the einsums, `attn_xla_calls` = depth, and
    no Mosaic call is in the lowered step."""
    depth, im = 3, 32
    fresh_cfg.OPTIM.OPTIMIZER = "lamb"
    mesh = data_mesh(1)
    model = ViT(patch=16, dim=128, depth=depth, num_heads=2, mlp_dim=64, num_classes=4,
                dtype=jnp.bfloat16)
    state, tx = trainer.create_train_state(model, jax.random.PRNGKey(0), mesh, im)
    step = trainer.make_train_step(model, tx, mesh, topk=2)
    vec = NamedSharding(mesh, P("data"))
    batch = {
        "image": jax.device_put(np.zeros((2, im, im, 3), np.float32),
                                NamedSharding(mesh, P("data", None, None, None))),
        "label": jax.device_put(np.zeros((2,), np.int32), vec),
        "weight": jax.device_put(np.ones((2,), np.float32), vec),
    }
    lowered = {}
    counters = _counted(lambda: lowered.setdefault(
        "text", step.lower(state, batch, jnp.float32(0.1), jax.random.PRNGKey(1)).as_text()))
    assert counters.get(attention.XLA_CALLS_EVENT) == depth
    assert attention.FUSED_CALLS_EVENT not in counters
    assert "tpu_custom_call" not in lowered["text"] and "dtpu_attn" not in lowered["text"]
