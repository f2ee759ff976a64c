"""Compile the trainer-reachable Pallas kernels for a described TPU v5e.

The TPU compiler is installed wherever libtpu is; it compiles for a chip
that is described (`topologies.get_topology_desc`) and not attached, so
these tests run under ``JAX_PLATFORMS=cpu`` and still raise what the chip's
compiler would raise — what the interpret-mode tests cannot see (tiling,
VMEM, layouts Mosaic refuses). Nothing runs: a passing compile says nothing
about results or times, and is not a chip run (`chip_smoke.py` is).

One file on purpose, and the topology is described inside a module fixture:
only the xdist worker that is handed this file loads the TPU library, and
it keeps it until it exits. Nothing here may touch the topology at import
or collection time, start a child that needs libtpu, or be ``autouse``.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from distribuuuu_tpu.ops import (
    fused_attention,
    fused_attention_abs,
    fused_conv_epilogue,
    fused_moe_combine,
    fused_moe_dispatch,
)

# resnet50's five stage widths at batch 64: (B, H, W, C) of the conv output
# `models/layers.bn_epilogue` hands the kernel
EPILOGUE_SHAPES = [
    (64, 56, 56, 64),
    (64, 56, 56, 256),
    (64, 28, 28, 512),
    (64, 14, 14, 1024),
    (64, 7, 7, 2048),
]
# (B, heads, L, d): botnet50's MHSA at 224 px, and the 4x-token case
ATTENTION_SHAPES = [(8, 4, 196, 128), (4, 4, 784, 64)]
# (B, L, heads, hd) of the packed-qkv pair: vit_b16.train's own shape, MAE's
# 50-token encoder and hd-32 decoder, vit_l16's width
SELF_ATTENTION_SHAPES = [(128, 197, 12, 64), (8, 50, 12, 64), (8, 197, 16, 32), (4, 197, 16, 64)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compile cache off:
    a compile for a described chip is written to the cache but cannot be
    read back without one, and the next run would warn about every entry."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, args, sharding, grad: bool) -> str:
    """Compile `fn` (or the gradient of its squared sum) for the described
    chip; returns the program text."""
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding) for a in args]
    program = fn
    if grad:
        def loss(*xs):
            return jnp.sum(fn(*xs).astype(jnp.float32) ** 2)

        program = jax.grad(loss, argnums=tuple(range(len(args))))
    return jax.jit(program).lower(*specs).compile().as_text()


def _struct(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("residual", [False, True], ids=["plain", "res"])
@pytest.mark.parametrize("shape", EPILOGUE_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_fused_epilogue_compiles_for_v5e(one_chip, shape, residual, grad):
    c = shape[-1]
    args = [_struct(shape, jnp.bfloat16)] + [_struct((c,), jnp.float32)] * 3
    if residual:
        args.append(_struct(shape, jnp.bfloat16))

    def fn(*xs):
        return fused_conv_epilogue(*xs, relu=True, bn_dtype=jnp.bfloat16, interpret=False)

    text = _compile(fn, args, one_chip, grad)
    # the forward kernel is in both programs (the VJP's forward rule runs it);
    # a VMEM guard that quietly took the XLA formulation would fail here
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("variant", ["bias", "abs"])
@pytest.mark.parametrize("bnld", ATTENTION_SHAPES, ids=lambda s: f"L{s[2]}d{s[3]}")
def test_fused_attention_compiles_for_v5e(one_chip, bnld, variant, grad):
    b, n, l, d = bnld
    qkv = [_struct((b, n, l, d), jnp.bfloat16)] * 3
    if variant == "bias":
        args = qkv + [_struct((b, n, l, l), jnp.float32)]
        kernel = fused_attention
    else:
        args = qkv + [_struct((l, d), jnp.float32)]
        kernel = fused_attention_abs

    def fn(*xs):
        return kernel(*xs, interpret=False)

    assert "tpu_custom_call" in _compile(fn, args, one_chip, grad)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("blhd", SELF_ATTENTION_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_fused_self_attention_compiles_for_v5e(one_chip, blhd, grad):
    from distribuuuu_tpu.ops.attention import fused_self_attention

    b, l, heads, hd = blhd
    text = _compile(
        lambda qkv: fused_self_attention(qkv, heads, False),
        [_struct((b, l, 3 * heads * hd), jnp.bfloat16)], one_chip, grad,
    )
    assert "dtpu_attn_fwd" in text and ("dtpu_attn_bwd" in text) is grad
    # nothing of size L x L outside the two kernels, in either direction
    assert f"{l},{l}]" not in text


def test_vit_step_for_described_v5e_takes_the_fused_pair(topo, one_chip):
    """The trainer's own step lowered for the described chip's mesh: every
    block takes the fused pair (`attn_fused_calls` = depth, no
    `attn_xla_calls`), and the kernels stand in the lowered step under their
    names. Only lowered: the whole-step compile is `chip_smoke.py`'s."""
    from distribuuuu_tpu import config, optim, trainer
    from distribuuuu_tpu.models.vit import ViT
    from distribuuuu_tpu.obs.monitors import MonitoringBridge
    from distribuuuu_tpu.ops import attention
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    depth, im = 3, 32
    config.reset_cfg()
    config.cfg.OPTIM.OPTIMIZER = "lamb"
    # conftest asks for the interpreter; the chip's route does not
    interpret = set_pallas_interpret(False)
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        model = ViT(patch=16, dim=128, depth=depth, num_heads=2, mlp_dim=64, num_classes=4,
                    dtype=jnp.bfloat16)
        tx = optim.construct_optimizer()

        def init(key):
            params = model.init(key, jnp.zeros((1, im, im, 3), jnp.float32), train=False)["params"]
            return trainer.TrainState(params=params, batch_stats={}, opt_state=tx.init(params))

        replicated = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P("data"))
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
            jax.eval_shape(init, jax.random.PRNGKey(0)),
        )
        batch = {
            "image": jax.ShapeDtypeStruct(
                (2, im, im, 3), jnp.float32,
                sharding=NamedSharding(mesh, P("data", None, None, None)),
            ),
            "label": jax.ShapeDtypeStruct((2,), jnp.int32, sharding=rows),
            "weight": jax.ShapeDtypeStruct((2,), jnp.float32, sharding=rows),
        }
        step = trainer.make_train_step(model, tx, mesh, topk=2)
        bridge = MonitoringBridge().install()
        try:
            text = step.lower(
                state, batch, jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated),
            ).as_text()
            counters = bridge.snapshot()["counters"]
        finally:
            bridge.close()
    finally:
        set_pallas_interpret(interpret)
        config.reset_cfg()
    assert counters.get(attention.FUSED_CALLS_EVENT) == depth
    assert attention.XLA_CALLS_EVENT not in counters
    assert "dtpu_attn_fwd" in text and "dtpu_attn_bwd" in text


# -- the token model's mixers at the widths of config/nemotron3_super.yaml: the scan is XLA's alone, the
# expert layer's products XLA's outside a mesh and the kernel pair of ops/grouped.py inside the chip's ----

def _compiles_without_kernels(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    return text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_chunked_scan_compiles_for_v5e_at_the_cells_sizes(one_chip, grad):
    """One row of 8192 positions, the 16 heads x 64 and the one B/C group of 128 states a chip holds."""
    from distribuuuu_tpu.ops.ssm import ssd_scan

    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    b, l, h, p, n = 1, 8192, 16, 64, 128
    args = (shape((b, l, h, p), jnp.bfloat16), shape((b, l, h), jnp.float32), shape((h,), jnp.float32),
            shape((b, l, 1, n), jnp.bfloat16), shape((b, l, 1, n), jnp.bfloat16), shape((h,), jnp.float32))
    scan = lambda *a: ssd_scan(*a, 128)
    fn = jax.grad(lambda *a: jnp.sum(scan(*a).astype(jnp.float32)), argnums=(0, 1, 3, 4)) if grad else scan
    assert "dtpu.ssm_scan" in _compiles_without_kernels(fn, *args)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_held_experts_compile_for_v5e_at_the_cells_sizes(topo, one_chip, route, grad):
    """8 of 512 experts, top-22, latent 1024, width 2688, 8192 tokens: round 0 and the overflow rounds.
    Outside any mesh a block's products are XLA's against gathered weights; inside the described
    chip's mesh, as the trainer's step traces them, they are the kernel pair of `ops/grouped.py`,
    which leaves no copy of the weights a block and no weight gradient a block in the program."""
    from distribuuuu_tpu.obs.monitors import MonitoringBridge
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret
    from distribuuuu_tpu.parallel import moe

    t, e, held, d, f = 8192, 512, 8, 1024, 2688
    rows = moe.round_rows_for(t, 22, e, held)
    blocks = rows // moe.BLOCK

    def mix(x, logits, w1, w2):
        idx, w = moe.sigmoid_topk_route(logits, 22, jnp.zeros((e,)), 5.0)
        y, counts = moe.held_experts(x, idx, w, w1, w2, 0, rows, between=moe.relu_squared)
        return y if not grad else jnp.sum(y)

    fn = jax.grad(mix, argnums=(0, 1, 2, 3)) if grad else mix
    sharding = one_chip
    if route == "kernels":
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        fn = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)  # as the trainer's steps are
        sharding = NamedSharding(mesh, P())
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=sharding)
    args = (shape((t, d), jnp.bfloat16), shape((t, e), jnp.float32),
            shape((held, d, f), jnp.float32), shape((held, f, d), jnp.float32))
    interpret = set_pallas_interpret(False)  # conftest asks for the interpreter; the chip's route does not
    bridge = MonitoringBridge().install()
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
        counters = bridge.snapshot()["counters"]
    finally:
        bridge.close()
        set_pallas_interpret(interpret)
    assert "dtpu.moe_route" in text and "dtpu.moe_experts" in text
    assert text.count(" conditional(") >= 3  # groups of 1, 2, 4, ... rounds of 6400 rows beyond the first
    copies = [f"[{blocks},{d},{f}]", f"[{blocks},{f},{d}]"]  # a block's own weights, or its own weight gradient
    if route == "xla":
        assert "tpu_custom_call" not in text and any(c in text for c in copies)
        assert moe.GROUPED_CALLS_EVENT not in counters and moe.XLA_CALLS_EVENT not in counters  # no mesh: uncounted
        return
    assert counters.get(moe.GROUPED_CALLS_EVENT, 0) >= 1 and moe.XLA_CALLS_EVENT not in counters
    assert "dtpu_moe_gmm" in text and ("dtpu_moe_tgmm" in text) is grad
    assert not any(c in text for c in copies)
    # every kernel call under the experts' scope, and no scatter-add of weight gradients left under the route's
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls and all("dtpu.moe_experts/" in line for line in calls)
    held_weights = (f"f32[{held},{d},{f}]", f"f32[{held},{f},{d}]")
    assert not [line for line in text.splitlines()
                if "dtpu.moe_route" in line and "scatter" in line and any(w in line for w in held_weights)]


# -- the second token model at the widths of config/qwen3_next.yaml: the delta rule's chunk inverse is a kernel
# pair inside a mesh of TPUs and XLA's outside; its expert layer's gated products are the grouped pair; the
# cell's whole step fits the chip at two rows -------------------------------------------------------------------

@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_chunked_delta_rule_compiles_for_v5e_at_the_cells_sizes(topo, one_chip, route, grad):
    """One row of 8192 positions, 32 value heads of 128 by 128, chunks of 64. Outside any mesh the chunks'
    inverses are XLA's batched products; inside the described chip's mesh, as the trainer's step traces them,
    the kernel pair of `ops/gdn_inverse.py`, which leaves no product of 4096 tiles of 64 x 64 float32 in the
    program."""
    from distribuuuu_tpu.obs.monitors import MonitoringBridge
    from distribuuuu_tpu.ops import gdn
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    b, l, h, k, v = 1, 8192, 32, 128, 128
    rule = lambda *a: gdn.gated_delta_rule(*a, 64)
    fn = jax.grad(lambda *a: jnp.sum(rule(*a).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)) if grad else rule
    sharding = one_chip
    if route == "kernels":
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        fn = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)  # as the trainer's steps are
        sharding = NamedSharding(mesh, P())
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    args = (shape((b, l, h, k), jnp.bfloat16), shape((b, l, h, k), jnp.bfloat16), shape((b, l, h, v), jnp.bfloat16),
            shape((b, l, h), jnp.float32), shape((b, l, h), jnp.float32))
    interpret = set_pallas_interpret(False)  # conftest asks for the interpreter; the chip's route does not
    bridge = MonitoringBridge().install()
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
        counters = bridge.snapshot()["counters"]
    finally:
        bridge.close()
        set_pallas_interpret(interpret)
    assert "dtpu.gdn_scan" in text
    # float32 products whose result is a tile a chunk and head (XLA prints a batched product as a convolution)
    tile_products = re.findall(rf"= f32\[(?:{l // 64 * h}|{l // 64},{h}|{l // 64},{b},{h}),64,64\]\S* (?:dot|convolution)\(", text)
    if route == "xla":
        assert "tpu_custom_call" not in text and len(tile_products) >= 10  # an inverse's ten and the few around it
        assert gdn.KERNEL_CALLS_EVENT not in counters and gdn.XLA_CALLS_EVENT not in counters  # no mesh: uncounted
        return
    assert counters.get(gdn.KERNEL_CALLS_EVENT, 0) >= 1 and gdn.XLA_CALLS_EVENT not in counters
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls and all("dtpu.gdn_scan" in line for line in calls)
    named = lambda name: [line for line in calls if f"/{name}/pallas_call" in line]
    assert named("dtpu_gdn_inverse") and bool(named("dtpu_gdn_inverse_bwd")) is grad
    # left with a tile's shape: k·kᵀ and q·kᵀ (and in the gradient the one that goes back through the decays)
    assert len(tile_products) <= 3


@pytest.mark.parametrize("tiles, q", [(4096, 64), (100, 8), (100, 16), (100, 48), (20, 128)],
                         ids=lambda v: str(v))
def test_chunk_inverse_kernels_compile_for_v5e_at_the_widths_the_choice_admits(one_chip, tiles, q):
    """The cell's own call (a row's 128 chunks x 32 heads of 64 x 64) and the ends of what `inverse_fits`
    admits, with tile counts that are no whole number of grid steps."""
    from distribuuuu_tpu.ops import gdn_inverse

    assert gdn_inverse.inverse_fits(next(iter(one_chip.device_set)).device_kind, q, jnp.float32)
    a = _struct((tiles, q, q), jnp.float32)
    assert "dtpu_gdn_inverse" in _compile(lambda a: gdn_inverse.inverse(a), [a], one_chip, grad=False)
    assert "dtpu_gdn_inverse_bwd" in _compile(lambda t, d: gdn_inverse.inverse_bwd(t, d), [a, a], one_chip, grad=False)


# -- the short convolution of both token families at the cells' widths: the kernel pair inside the described
# chip's mesh, forward and backward, five operands a call at the most ----------------------------------------

@pytest.mark.parametrize("case", ["qwen3_next", "nemotron3_super"])
def test_short_conv_pair_compiles_for_v5e_at_the_cells_sizes(topo, case):
    """qwen3_next's two rows of 8192 positions of 8192 channels (bfloat16 in, float32 out, no bias) and
    nemotron3_super's one row of 1280 channels (float32 in, bfloat16 out, a bias), through the entry point inside
    a mesh of the described chip as the trainer's steps trace it: the route takes the pair, forward and backward,
    counts it, and every call stands under the op's scope."""
    from distribuuuu_tpu.obs.monitors import MonitoringBridge
    from distribuuuu_tpu.ops import short_conv
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    (rows, channels, x_dtype, out_dtype), bias = {
        "qwen3_next": ((2, 8192, jnp.bfloat16, jnp.float32), False),
        "nemotron3_super": ((1, 1280, jnp.float32, jnp.bfloat16), True)}[case]
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, P()))
    args = [shape((rows, 8192, channels), x_dtype), shape((4, channels), jnp.float32)] + [shape((channels,), jnp.float32)] * bias
    loss = lambda *a: jnp.sum(jnp.sin(short_conv.causal_conv_silu(*a, out_dtype=out_dtype).astype(jnp.float32)))
    fn = jax.shard_map(jax.grad(loss, argnums=tuple(range(len(args)))), mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    interpret = set_pallas_interpret(False)  # conftest asks for the interpreter; the chip's route does not
    bridge = MonitoringBridge().install()
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
        counters = bridge.snapshot()["counters"]
    finally:
        bridge.close()
        set_pallas_interpret(interpret)
    assert counters.get(short_conv.KERNEL_CALLS_EVENT, 0) >= 1 and short_conv.XLA_CALLS_EVENT not in counters
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.search(r"/(dtpu_short_conv_\w+)/pallas_call", c).group(1) for c in calls) == [
        "dtpu_short_conv_bwd", "dtpu_short_conv_fwd"]
    assert all("dtpu.short_conv" in c for c in calls)
    # five operands at the most: the benchmark's reader of kernel calls takes no list XLA marks /*index=5*/
    assert not any("/*index=" in re.search(r"custom-call\(([^)]*)\)", c).group(1) for c in calls)


def test_qwen3_next_step_compiles_for_v5e_and_fits_beside_the_benchmarks_copy(topo, one_chip, fresh_cfg):
    """The cell's own step (config/qwen3_next.yaml: two rows of 8192 tokens, 626 M parameters, Adafactor,
    the layer checkpoint) compiled for the described chip: the compiler accepts it, and by its own count the
    step's peak (the state and what it holds beside it) leaves room on the chip's 15.75 GiB for the
    benchmark's second copy of the weights; the held experts' gated products are the kernel
    pair, forward and backward; every model scope stands in it. Its arguments are the state: parameters and
    a few MB."""
    from distribuuuu_tpu import optim, trainer
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    cfg = fresh_cfg  # restores the config and the model-trace globals the build sets from it (bfloat16 norms)
    cfg.merge_from_file(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                     "config", "qwen3_next.yaml"))
    interpret = set_pallas_interpret(False)  # conftest asks for the interpreter; the chip's route does not
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        model = trainer._build_cfg_model()
        tx = optim.construct_optimizer()

        def init(key):
            variables = model.init(key, model.dummy_input(0), train=False)
            return trainer.TrainState(params=variables["params"], batch_stats={}, opt_state=tx.init(variables["params"]))

        replicated = NamedSharding(mesh, P())
        struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
        state = jax.tree.map(struct, jax.eval_shape(init, jax.random.key(0)))
        rows = cfg.TRAIN.BATCH_SIZE
        batch = {"tokens": jax.ShapeDtypeStruct((rows, cfg.LM.SEQ_LEN + 1), jnp.int32,
                                                sharding=NamedSharding(mesh, P("data", None)))}
        step = trainer.make_train_step(model, tx, mesh, topk=5)
        compiled = step.lower(state, batch, struct(jnp.float32(0.0)), struct(jax.eval_shape(lambda: jax.random.key(1)))).compile()
    finally:
        set_pallas_interpret(interpret)
    memory = compiled.memory_analysis()
    weights = 4 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params))
    assert rows == 2 and weights == 4 * 625_667_136
    assert weights <= memory.argument_size_in_bytes <= weights + 64 * 2**20  # the state: Adafactor's is rows and columns
    assert memory.alias_size_in_bytes >= weights  # donated: the new state takes the old one's place
    peak = memory.peak_memory_in_bytes  # the state and, beside it, the most the step holds at a time
    assert peak + weights <= 15.75 * 2**30, f"{peak / 2**30:.2f} GiB beside a second copy of {weights / 2**30:.2f}"
    text = compiled.as_text()
    assert "dtpu_moe_gmm" in text and "dtpu_moe_tgmm" in text
    assert "dtpu_causal_attn_fwd" in text and "dtpu_causal_attn_bwd" in text  # the gated attention's core
    assert "dtpu_short_conv_fwd" in text and "dtpu_short_conv_bwd" in text  # the delta-net mixers' convolution
    for scope in ("dtpu.gdn_scan", "dtpu.causal_attn", "dtpu.mixer_proj", "dtpu.dense_ffn", "dtpu.moe_route",
                  "dtpu.moe_experts", "dtpu.lm_head", "dtpu.short_conv", "dtpu.optimizer", "dtpu.loss"):
        assert scope in text, scope


# -- the third token model at the widths of config/kanana2_30b.yaml: latent attention's causal core is the causal
# pair, the expert layers' gated products the grouped pair, the leading dense layer stands before one scanned
# unit; the cell's whole step fits the chip at two rows -----------------------------------------------------

def test_kanana2_30b_step_compiles_for_v5e_and_fits_beside_the_benchmarks_copy(topo, one_chip, fresh_cfg):
    """The cell's own step (config/kanana2_30b.yaml: two rows of 8192 tokens, 576 M parameters, Adafactor, the
    layer checkpoint) compiled for the described chip: the compiler accepts it, and by its own count the step's
    peak (the state and what it holds beside it) leaves room on the chip's 15.75 GiB for the benchmark's second
    copy of the weights; the four expert layers are one loop's body, so the step holds the core's scope in two
    layers' worth of ops and not five; the core is the causal pair under its scope, the forward once a layer
    (`KEPT` keeps its output and log-sum-exp), and the held experts' gated products the grouped pair; every
    model scope stands in it."""
    from distribuuuu_tpu import optim, trainer
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    cfg = fresh_cfg  # restores the config and the model-trace globals the build sets from it (bfloat16 norms)
    cfg.merge_from_file(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                     "config", "kanana2_30b.yaml"))
    interpret = set_pallas_interpret(False)  # conftest asks for the interpreter; the chip's route does not
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        model = trainer._build_cfg_model()
        tx = optim.construct_optimizer()

        def init(key):
            variables = model.init(key, model.dummy_input(0), train=False)
            return trainer.TrainState(params=variables["params"], batch_stats=variables["batch_stats"],
                                      opt_state=tx.init(variables["params"]))

        replicated = NamedSharding(mesh, P())
        struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
        state = jax.tree.map(struct, jax.eval_shape(init, jax.random.key(0)))
        rows = cfg.TRAIN.BATCH_SIZE
        batch = {"tokens": jax.ShapeDtypeStruct((rows, cfg.LM.SEQ_LEN + 1), jnp.int32,
                                                sharding=NamedSharding(mesh, P("data", None)))}
        step = trainer.make_train_step(model, tx, mesh, topk=5)
        compiled = step.lower(state, batch, struct(jnp.float32(0.0)), struct(jax.eval_shape(lambda: jax.random.key(1)))).compile()
    finally:
        set_pallas_interpret(interpret)
    memory = compiled.memory_analysis()
    weights = 4 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params))
    assert rows == 2 and weights == 4 * 575_955_456
    assert {k: v.shape for k, v in state.batch_stats.items()} == {"U0_b_corr": (4, 128)}
    assert weights <= memory.argument_size_in_bytes <= weights + 64 * 2**20  # the state: Adafactor's is rows and columns
    assert memory.alias_size_in_bytes >= weights  # donated: the new state takes the old one's place
    peak = memory.peak_memory_in_bytes  # the state and, beside it, the most the step holds at a time
    assert peak + weights <= 15.75 * 2**30, f"{peak / 2**30:.2f} GiB beside a second copy of {weights / 2**30:.2f}"
    text = compiled.as_text()
    assert "dtpu_moe_gmm" in text and "dtpu_moe_tgmm" in text
    for scope in ("dtpu.latent_attn", "dtpu.causal_attn", "dtpu.mixer_proj", "dtpu.dense_ffn", "dtpu.moe_route",
                  "dtpu.moe_experts", "dtpu.lm_head", "dtpu.optimizer", "dtpu.loss"):
        assert scope in text, scope
    # the core's products stand under L0 (the leading layer, unrolled) and U0 (the unit, once) and under no other layer
    layers_of_the_core = set(re.findall(r"/(L\d+|U\d+)/dtpu\.latent_attn/", text))
    assert layers_of_the_core == {"L0", "U0"}
    calls = [re.search(r'op_name="[^"]*/dtpu\.latent_attn/(?:dtpu\.causal_attn/)+(dtpu_causal_attn_\w+)/pallas_call"', line)
             for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line and "dtpu_causal" in line]
    assert sorted(c.group(1) for c in calls) == ["dtpu_causal_attn_bwd"] * 2 + ["dtpu_causal_attn_fwd"] * 2
    # five operands at the most: the benchmark's reader of kernel calls takes no list XLA marks /*index=5*/
    assert not any("/*index=" in re.search(r"custom-call\(([^)]*)\)", c.string).group(1) for c in calls)


# -- the fourth token model at the widths of config/ling3_flash.yaml: the Kimi delta rule's within-chunk products
# are `ops/kda_terms.py`'s kernel pair and its chunk inverse the delta rule's inside a mesh of TPUs; the cell's whole
# step fits the chip at one row ---------------------------------------------------------------------------------

@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_kimi_delta_rule_compiles_for_v5e_at_the_cells_sizes(topo, one_chip, route):
    """One row of 8192 positions, 32 heads of 128 by 128, chunks of 64, the gradient: outside any mesh the
    within-chunk products and the chunks' inverses are XLA's, inside the described chip's mesh `ops/kda_terms.py`'s
    pair beside `ops/gdn_inverse.py`'s, each counted under its own events; every call stands under the rule's
    scope and takes five operands at the most."""
    from distribuuuu_tpu.obs.monitors import MonitoringBridge
    from distribuuuu_tpu.ops import gdn, kda
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    b, l, h, k, v = 1, 8192, 32, 128, 128
    fn = jax.grad(lambda *a: jnp.sum(kda.kimi_delta_rule(*a, 64).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4))
    sharding = one_chip
    if route == "kernels":
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        fn = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)  # as the trainer's steps are
        sharding = NamedSharding(mesh, P())
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    args = (shape((b, l, h, k), jnp.bfloat16), shape((b, l, h, k), jnp.bfloat16), shape((b, l, h, v), jnp.bfloat16),
            shape((b, l, h, k), jnp.float32), shape((b, l, h), jnp.float32))
    interpret = set_pallas_interpret(False)  # conftest asks for the interpreter; the chip's route does not
    bridge = MonitoringBridge().install()
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
        counters = bridge.snapshot()["counters"]
    finally:
        bridge.close()
        set_pallas_interpret(interpret)
    assert "dtpu.kda_scan" in text
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    if route == "xla":
        assert not calls  # no mesh: uncounted
        assert not {gdn.KERNEL_CALLS_EVENT, kda.KERNEL_CALLS_EVENT, kda.XLA_CALLS_EVENT} & set(counters)
        return
    assert counters.get(gdn.KERNEL_CALLS_EVENT, 0) >= 1 and gdn.XLA_CALLS_EVENT not in counters
    assert counters.get(kda.KERNEL_CALLS_EVENT, 0) >= 1 and kda.XLA_CALLS_EVENT not in counters
    assert calls and all("dtpu.kda_scan" in line for line in calls)
    assert {re.search(r"/(dtpu_\w+)/pallas_call", line).group(1) for line in calls} == {
        "dtpu_gdn_inverse", "dtpu_gdn_inverse_bwd", "dtpu_kda_terms", "dtpu_kda_terms_bwd"}
    # five operands at the most: the benchmark's reader of kernel calls takes no list XLA marks /*index=5*/
    assert not any("/*index=" in re.search(r"custom-call\(([^)]*)\)", line).group(1) for line in calls)


def test_ling3_flash_step_compiles_for_v5e_and_fits_beside_the_benchmarks_copy(topo, one_chip, fresh_cfg):
    """The cell's own step (config/ling3_flash.yaml: one row of 8192 tokens, 884.5 M parameters, Adafactor, the
    layer checkpoint) compiled for the described chip: the compiler accepts it, and by its own count the step's
    peak (the state and what it holds beside it) leaves room on the chip's 15.75 GiB for the benchmark's second
    copy of the weights; the five delta-attention layers are one loop's body; the rule's inverse, the short
    convolution, latent attention's core and the held experts' gated products are their kernel pairs, and so are the
    rule's within-chunk products; every model scope stands in it."""
    from distribuuuu_tpu import optim, trainer
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    cfg = fresh_cfg  # restores the config and the model-trace globals the build sets from it (bfloat16 norms)
    cfg.merge_from_file(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                     "config", "ling3_flash.yaml"))
    interpret = set_pallas_interpret(False)  # conftest asks for the interpreter; the chip's route does not
    try:
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        model = trainer._build_cfg_model()
        tx = optim.construct_optimizer()

        def init(key):
            variables = model.init(key, model.dummy_input(0), train=False)
            return trainer.TrainState(params=variables["params"], batch_stats=variables["batch_stats"],
                                      opt_state=tx.init(variables["params"]))

        replicated = NamedSharding(mesh, P())
        struct = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
        state = jax.tree.map(struct, jax.eval_shape(init, jax.random.key(0)))
        rows = cfg.TRAIN.BATCH_SIZE
        batch = {"tokens": jax.ShapeDtypeStruct((rows, cfg.LM.SEQ_LEN + 1), jnp.int32,
                                                sharding=NamedSharding(mesh, P("data", None)))}
        step = trainer.make_train_step(model, tx, mesh, topk=5)
        compiled = step.lower(state, batch, struct(jnp.float32(0.0)), struct(jax.eval_shape(lambda: jax.random.key(1)))).compile()
    finally:
        set_pallas_interpret(interpret)
    memory = compiled.memory_analysis()
    weights = 4 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params))
    assert rows == 1 and weights == 4 * 884_456_768
    assert {k: v.shape for k, v in state.batch_stats.items()} == {"U0_b_corr": (5, 512), "L6_b_corr": (512,)}
    assert weights <= memory.argument_size_in_bytes <= weights + 64 * 2**20  # the state: Adafactor's is rows and columns
    assert memory.alias_size_in_bytes >= weights  # donated: the new state takes the old one's place
    peak = memory.peak_memory_in_bytes  # the state and, beside it, the most the step holds at a time
    assert peak + weights <= 15.75 * 2**30, f"{peak / 2**30:.2f} GiB beside a second copy of {weights / 2**30:.2f}"
    text = compiled.as_text()
    kernels = set(re.findall(r"/(dtpu_\w+)/pallas_call", text))
    assert kernels == {"dtpu_gdn_inverse", "dtpu_gdn_inverse_bwd", "dtpu_kda_terms", "dtpu_kda_terms_bwd",
                       "dtpu_short_conv_fwd", "dtpu_short_conv_bwd", "dtpu_causal_attn_fwd", "dtpu_causal_attn_bwd",
                       "dtpu_moe_gmm", "dtpu_moe_tgmm"}
    for scope in ("dtpu.kda_scan", "dtpu.short_conv", "dtpu.latent_attn", "dtpu.causal_attn", "dtpu.mixer_proj",
                  "dtpu.dense_ffn", "dtpu.moe_route", "dtpu.moe_experts", "dtpu.lm_head", "dtpu.optimizer", "dtpu.loss"):
        assert scope in text, scope
    # the rule stands under the leading layer (unrolled) and the unit (once), and under no other layer
    assert set(re.findall(r"/(L\d+|U\d+)/dtpu\.kda_scan/", text)) == {"L0", "U0"}


def test_causal_pair_compiles_for_v5e_at_the_grouped_cores_smallest_shape(topo, one_chip):
    """`nemotron3_super.train`'s core (one row of 8192 tokens, 4 query heads of 128 over one key head), through
    the entry point inside a mesh of the described chip: the route takes the pair, forward and backward."""
    from distribuuuu_tpu.ops import attention
    from distribuuuu_tpu.ops.interpret import set_pallas_interpret

    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    args = [_struct((1, 8192, heads, 128), jnp.bfloat16) for heads in (4, 1, 1)]
    interpret = set_pallas_interpret(False)
    try:
        with jax.set_mesh(mesh):
            text = _compile(attention.causal_attention, args, NamedSharding(mesh, P()), grad=True)
    finally:
        set_pallas_interpret(interpret)
    assert "dtpu_causal_attn_fwd" in text and "dtpu_causal_attn_bwd" in text


# ops/moe_kernel.py is refused by Mosaic at every shape its VMEM guard admits
# (larger ones hand over to the einsum formulation before the kernel is
# reached). No trainer path calls it; repair or deletion belongs to the first
# MoE model_config issue (ROADMAP Design-2 / Speed-5). Strict: the day either
# compiles, this file says so.
_MOE = dict(n=1024, d=512, e=8, c=160)


@pytest.mark.xfail(
    strict=True,
    raises=ValueError,
    reason="Mosaic lowering: ValueError: Can only store scalars to SMEM "
    "(fused_moe_dispatch at n=1024, D=512, E=8, C=160)",
)
def test_moe_dispatch_compiles_for_v5e(one_chip):
    args = [_struct((_MOE["n"], _MOE["d"]), jnp.bfloat16), _struct((_MOE["d"], _MOE["e"]), jnp.float32)]

    def fn(x, gate):
        return fused_moe_dispatch(x, gate, capacity=_MOE["c"], interpret=False)[0]

    _compile(fn, args, one_chip, grad=False)


@pytest.mark.xfail(
    strict=True,
    reason="MosaicError: infer-vector-layout: unsupported shape cast, tpu.reshape "
    "(vector<1x128xf32>) -> vector<128x1x1xf32> (fused_moe_combine at n=1024, "
    "D=512, E=8, C=160)",
)
def test_moe_combine_compiles_for_v5e(one_chip):
    n, d, e, c = (_MOE[k] for k in "ndec")
    args = [
        _struct((e, c, d), jnp.float32),
        _struct((n,), jnp.int32),
        _struct((n,), jnp.int32),
        _struct((n,), jnp.float32),
    ]

    def fn(back, top, pos, w):
        return fused_moe_combine(back, top, pos, w, interpret=False)

    _compile(fn, args, one_chip, grad=False)
