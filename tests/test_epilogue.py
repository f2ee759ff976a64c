"""Fused conv-epilogue kernels (ops/epilogue.py): oracle equality + routing.

Tiers:

- **kernel units** — interpret-mode oracle equality (fwd + grad) over the
  boundary shape matrix: ragged row tiles, narrow/edge channel counts, bf16
  and f32 BN-boundary dtypes, residual and non-residual, relu on/off.
- **model tier** — the real contract: resnet blocks traced FUSED are
  bitwise the UNFUSED (`nn.BatchNorm` + add + relu) path — eval forward,
  train-mode gradients, and the updated batch statistics — including the
  SyncBN pmean under a 2-device shard_map and the zero-init-residual BN.
- **routing/guard** — the run default routes the model (the rule itself is
  tests/test_kernel_choice.py), the VMEM-budget fallback's identical
  numerics + counted fallbacks, and fused/unfused variable-tree identity
  (checkpoints trained one way load the other).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distribuuuu_tpu.ops.epilogue import (
    _VMEM_GUARD,
    fused_conv_epilogue,
    oracle_epilogue,
    set_fused_epilogue_default,
)


@pytest.fixture()
def fused_routing():
    """Flip the module routing default on, restore on exit."""
    set_fused_epilogue_default(True)
    try:
        yield
    finally:
        set_fused_epilogue_default(False)


def _assert_close(a, b):
    """Oracle-equality up to XLA's FMA liberty.

    The kernel body and the oracle are the same operation sequence, but XLA
    contracts ``(x−mean)·mul`` + add into an FMA when it jits the unfused
    form and the Pallas interpreter evaluates op-by-op — a ≤1-ulp
    reassociation XLA applies just as freely between any two traces of the
    unfused path itself. Tolerance = a few ulps of the *output* dtype at
    the value scale; f32 asserts at 1e-5 relative.
    """
    a = np.asarray(a)
    b = np.asarray(b)
    assert a.dtype == b.dtype
    rtol = 2.0**-6 if a.dtype == np.dtype(jnp.bfloat16) else 1e-5
    a32, b32 = a.astype(np.float32), b.astype(np.float32)
    atol = rtol * max(1.0, float(np.max(np.abs(b32))))
    np.testing.assert_allclose(a32, b32, rtol=rtol, atol=atol)


def _affine(rng, c):
    mean = jnp.asarray(rng.standard_normal(c), jnp.float32)
    var = jnp.asarray(np.abs(rng.standard_normal(c)) + 0.1, jnp.float32)
    scale = jnp.asarray(rng.standard_normal(c), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(c), jnp.float32)
    mul = jax.lax.rsqrt(var + 1e-5) * scale
    return mean, mul, bias


# ---------------------------------------------------------------------------
# kernel units: interpret-mode oracle equality
# ---------------------------------------------------------------------------

# covering design over the boundary matrix (a full 4×3×4 cross is ~50
# interpret-mode compiles for no extra coverage): every (shape, dtype-combo)
# pair appears, and each of shapes/dtypes cycles through all four
# residual×relu variants — ragged tiles meet residual AND non-residual,
# every dtype boundary meets relu-off, etc.
_SHAPES = [
    (64, 128, 32),    # exact tiling
    (67, 128, 32),    # ragged last tile
    (5, 24, 256),     # r < block AND an edge (non-lane-aligned) channel dim
    (130, 48, 128),   # ragged + narrow channels
]
_DTYPES = [
    (jnp.bfloat16, jnp.bfloat16),
    (jnp.bfloat16, jnp.float32),
    (jnp.float32, jnp.float32),
]
_VARIANTS = [(False, True), (True, True), (True, False), (False, False)]
_MATRIX = [
    (*_SHAPES[s], *_DTYPES[d], *_VARIANTS[(s + d) % 4])
    for s in range(len(_SHAPES))
    for d in range(len(_DTYPES))
]


@pytest.mark.parametrize("r,c,block,x_dtype,bn_dtype,residual,relu", _MATRIX)
def test_kernel_oracle_equality_fwd_and_grad(r, c, block, x_dtype, bn_dtype, residual, relu):
    rng = np.random.default_rng(r * 1000 + c)
    x = jnp.asarray(rng.standard_normal((r, c)), x_dtype)
    mean, mul, bias = _affine(rng, c)
    identity = (
        jnp.asarray(rng.standard_normal((r, c)), bn_dtype) if residual else None
    )

    def fused(*args):
        x_, me, mu, bi = args[:4]
        id_ = args[4] if residual else None
        return fused_conv_epilogue(
            x_, me, mu, bi, id_, relu=relu, bn_dtype=bn_dtype,
            block_rows=block, interpret=True,
        )

    def oracle(*args):
        x_, me, mu, bi = args[:4]
        id_ = args[4] if residual else None
        return oracle_epilogue(x_, me, mu, bi, id_, relu=relu, bn_dtype=bn_dtype)

    args = (x, mean, mul, bias) + ((identity,) if residual else ())
    out_f = np.asarray(fused(*args))
    out_o = np.asarray(oracle(*args))
    assert out_f.dtype == out_o.dtype
    _assert_close(out_f, out_o)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    gf = jax.grad(loss(fused), argnums=tuple(range(len(args))))(*args)
    go = jax.grad(loss(oracle), argnums=tuple(range(len(args))))(*args)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(go)):
        _assert_close(a, b)


def test_kernel_accepts_nhwc_and_preserves_shape():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 7, 7, 32)), jnp.bfloat16)
    mean, mul, bias = _affine(rng, 32)
    out = fused_conv_epilogue(
        x, mean, mul, bias, relu=True, bn_dtype=jnp.bfloat16,
        block_rows=16, interpret=True,
    )
    assert out.shape == x.shape and out.dtype == jnp.bfloat16
    want = oracle_epilogue(x, mean, mul, bias, relu=True, bn_dtype=jnp.bfloat16)
    _assert_close(out, want)


# ---------------------------------------------------------------------------
# model tier: fused resnet == unfused resnet, bitwise
# ---------------------------------------------------------------------------

def _rn18(num_classes=8, dtype=jnp.float32):
    from distribuuuu_tpu.convert import synthetic_variables
    from distribuuuu_tpu.models import build_model

    model = build_model("resnet18", num_classes=num_classes, dtype=dtype)
    v = synthetic_variables("resnet18", 7, 32, num_classes)
    return model, {"params": v["params"], "batch_stats": v["batch_stats"]}


@pytest.mark.parametrize("bn_dtype", ["float32", "bfloat16"])
def test_resnet18_eval_forward_bitwise_fused_vs_unfused(bn_dtype):
    from distribuuuu_tpu.convert import golden_inputs
    from distribuuuu_tpu.models.layers import (
        get_bn_compute_dtype,
        set_bn_compute_dtype,
    )

    prev = get_bn_compute_dtype()
    set_bn_compute_dtype(jnp.bfloat16 if bn_dtype == "bfloat16" else jnp.float32)
    try:
        dtype = jnp.bfloat16 if bn_dtype == "bfloat16" else jnp.float32
        model, variables = _rn18(dtype=dtype)
        x = jnp.asarray(golden_inputs(4, 32, 0))
        unfused = np.asarray(model.apply(variables, x, train=False))
        set_fused_epilogue_default(True)
        try:
            fused = np.asarray(model.apply(variables, x, train=False))
        finally:
            set_fused_epilogue_default(False)
        np.testing.assert_array_equal(fused, unfused)
    finally:
        set_bn_compute_dtype(prev)


def test_resnet18_train_grads_and_stats_bitwise():
    """Train mode: loss, every parameter gradient, and the EMA'd batch
    statistics are bitwise-identical fused vs unfused — the batch-stat
    computation (and its gradient) lives outside the kernel by design."""
    from distribuuuu_tpu.convert import golden_inputs

    model, variables = _rn18()
    x = jnp.asarray(golden_inputs(4, 32, 1))

    def loss(params, fused):
        set_fused_epilogue_default(fused)
        try:
            out, mut = model.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"],
            )
            return jnp.sum(out.astype(jnp.float32) ** 2), mut["batch_stats"]
        finally:
            set_fused_epilogue_default(False)

    (l0, s0), g0 = jax.value_and_grad(loss, has_aux=True)(variables["params"], False)
    (l1, s1), g1 = jax.value_and_grad(loss, has_aux=True)(variables["params"], True)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_syncbn_block_bitwise_under_shard_map(fused_routing):
    """SyncBN semantics are untouched: a BasicBlock with a BN axis_name,
    shard_mapped over 2 devices, produces bitwise-identical outputs and
    batch stats fused vs unfused (the stats pmean runs in flax code on both
    routes). f32 trunk: under jit, XLA:CPU elides intermediate bf16
    roundings *inside* its own fusions — a liberty a kernel boundary
    pins down — so a bf16 trunk differs by bf16 ulps between any two
    fusion decompositions; f32 has no such elision and stays bitwise."""
    from distribuuuu_tpu.models.resnet import BasicBlock
    from distribuuuu_tpu.runtime import data_mesh

    mesh = data_mesh(2)
    block = BasicBlock(
        planes=16, stride=1, downsample=True, bn_axis_name="data",
        dtype=jnp.float32,
    )
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 8)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x[:1], train=False)

    def run(fused):
        set_fused_epilogue_default(fused)
        try:
            def fwd(v, xs):
                out, mut = block.apply(v, xs, train=True, mutable=["batch_stats"])
                return out, mut["batch_stats"]

            sharded = jax.shard_map(
                fwd, mesh=mesh, in_specs=(P(), P("data")),
                out_specs=(P("data"), P()), check_vma=False,
            )
            jitted = jax.jit(sharded)
            return jitted(variables, x)
        finally:
            set_fused_epilogue_default(False)

    out_u, stats_u = jax.device_get(run(False))
    out_f, stats_f = jax.device_get(run(True))
    _assert_close(out_f, out_u)
    for a, b in zip(jax.tree.leaves(stats_f), jax.tree.leaves(stats_u)):
        # stats come from the SAME flax code on both routes — bitwise
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_init_residual_bn_fused_matches(fused_routing):
    """The zero-γ last BN of a residual block routes its scale_init through
    EpilogueBatchNorm: fused init == unfused init (zeros where expected)."""
    from distribuuuu_tpu.models.resnet import BasicBlock

    block = BasicBlock(planes=8, zero_init_residual=True, dtype=jnp.float32)
    x = jnp.zeros((1, 4, 4, 8), jnp.float32)
    v_fused = block.init(jax.random.PRNGKey(0), x, train=False)
    set_fused_epilogue_default(False)
    v_plain = block.init(jax.random.PRNGKey(0), x, train=False)
    a, b = jax.tree.leaves(v_fused), jax.tree.leaves(v_plain)
    assert jax.tree.structure(v_fused) == jax.tree.structure(v_plain)
    for x_, y_ in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x_), np.asarray(y_))
    assert float(jnp.max(jnp.abs(v_fused["params"]["bn2"]["scale"]))) == 0.0


# ---------------------------------------------------------------------------
# routing + guard
# ---------------------------------------------------------------------------

def test_cfg_routes_model():
    """``MODEL.FUSED_EPILOGUE`` (as `set_fused_epilogue_default` holds it)
    alone flips the model route — and the output stays bitwise."""
    from distribuuuu_tpu.convert import golden_inputs

    model, variables = _rn18()
    x = jnp.asarray(golden_inputs(2, 32, 5))
    plain = np.asarray(model.apply(variables, x, train=False))
    fallbacks = _VMEM_GUARD.fallbacks
    set_fused_epilogue_default(True)
    try:
        fused = np.asarray(model.apply(variables, x, train=False))
    finally:
        set_fused_epilogue_default(False)
    assert _VMEM_GUARD.fallbacks == fallbacks  # tiny tiles: kernel ran
    np.testing.assert_array_equal(fused, plain)


def test_vmem_guard_falls_back_identically(monkeypatch):
    """Over-budget tiles fall back to the oracle formulation: counted,
    warned once, numerically identical."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((64, 128)), jnp.bfloat16)
    mean, mul, bias = _affine(rng, 128)
    want = np.asarray(
        oracle_epilogue(x, mean, mul, bias, relu=True, bn_dtype=jnp.bfloat16)
    )
    monkeypatch.setenv("DTPU_EPILOGUE_VMEM_BUDGET_MB", "0.0001")
    before = _VMEM_GUARD.fallbacks
    got = np.asarray(
        fused_conv_epilogue(
            x, mean, mul, bias, relu=True, bn_dtype=jnp.bfloat16, interpret=True
        )
    )
    assert _VMEM_GUARD.fallbacks == before + 1
    np.testing.assert_array_equal(got, want)


def test_fused_and_unfused_variable_trees_identical(fused_routing):
    """Checkpoint compatibility: the fused route creates the same variable
    tree (paths, shapes, dtypes) as the unfused one — a fused-trained
    checkpoint loads unfused and vice versa."""
    from distribuuuu_tpu.models import build_model

    model = build_model("resnet18", num_classes=4, dtype=jnp.float32)
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    v_fused = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False)
    )
    set_fused_epilogue_default(False)
    v_plain = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False)
    )
    assert jax.tree.structure(v_fused) == jax.tree.structure(v_plain)
    for a, b in zip(jax.tree.leaves(v_fused), jax.tree.leaves(v_plain)):
        assert a.shape == b.shape and a.dtype == b.dtype
