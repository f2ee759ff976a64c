"""The sliding-window / full-attention token model (models/laguna.py) and what it brought, against the plain
reference ``tests/reference/laguna.py``: YaRN's frequencies; the rotary embedding and the softmax router with
their defaults as they were; loss and every gradient leaf; the layer checkpoint; two optimizer steps through
the trainer; the shares of an expert block add up to the uncut block; the shipped configuration's size. The
window's core itself is `tests/test_ops_causal_attention.py`'s. CPU, toy widths, seeded weights."""

from __future__ import annotations

import functools
import importlib.util
import math
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from distribuuuu_tpu import optim, trainer
from distribuuuu_tpu.ops.attention import partial_rotary, yarn_inv_freq
from distribuuuu_tpu.parallel import moe
from distribuuuu_tpu.runtime import data_mesh

import _token_layers

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(HERE, "reference", "laguna.py"), "reference_laguna")

# toy widths, the window shorter than the rows and YaRN's ramp inside the rotated pairs; the counts are the
# *uncut* block's, of which the share test takes parts
FULL = dict(vocab=48, dim=32, full_heads=4, window_heads=6, kv_heads=2, head_dim=16, window=7, rope_share=0.5,
            rope_theta=500.0, yarn_factor=8.0, yarn_original_positions=64, yarn_beta_fast=8.0, yarn_beta_slow=1.0,
            yarn_attention_factor=1.2, window_rope_theta=1e4, dense_width=40, experts=16, experts_held=16,
            expert_first=0, top_k=3, expert_width=24, shared_width=24, routed_scale=2.5, eps=1e-6)
SHARE = dict(FULL, experts_held=4, expert_first=4)  # one chip's share of it: a quarter of the experts
ROWS, LENGTH = 2, 20
STARTS_AT_ONE = ("norm", "post_norm", "q_norm", "k_norm", "norm_f")
ROUTER_SPREAD = 25.0
# bfloat16's bound on a router's gradient and on the post-norm weight before it: the router's gradient is the small
# difference that the renormalised softmax weights leave of the held experts' outputs, each rounded to bfloat16 in
# the program and not in the reference, so the rounding reads large beside it (0.06 to 0.31 at these sizes, the
# post-norm weights 0.05 to 0.24); in float32 the same leaves agree to 2e-4
ROUTING_BF16 = 0.4


def lg():
    """The program's module, imported when a test asks: it registers an arch, and ``list_models()`` is
    a parametrisation of other files."""
    from distribuuuu_tpu.models import laguna

    return laguna


def model_of(pattern: str, sizes: dict, dtype=jnp.float32, remat: bool = True):
    m = lg()
    return m.Laguna(m.Sizes(pattern=pattern, **sizes), dtype=dtype, remat=remat)


def to_program(params: dict, model) -> dict:
    """The reference's per-layer leaves (``L1.w1``) in the program's flat tree (``U0_w1 [repeats, ...]``)."""
    layers_of = _token_layers.layers_of(model.sizes.pattern)

    def leaf(name):
        prefix, _, short = name.partition("_")
        if prefix not in layers_of:
            return params[name]
        where = layers_of[prefix]
        if isinstance(where, list):
            return jnp.stack([params[f"L{i}.{short}"] for i in where])
        return params[f"L{where}.{short}"]

    return {name: leaf(name) for name in lg().param_shapes(model.sizes)}


def seeded(sizes: dict, seed: int = 3) -> dict:
    """The reference's weights, with the leaves that start at 1 moved off it so that each counts, and the
    routers' drawn wide (x `ROUTER_SPREAD`): at 0.02 some token's top-k stands within bfloat16's rounding of a
    tie, and a choice that flips moves a held expert's gradient by a whole token's part, which is another
    routing and no rounding."""
    return _seeded(tuple(sorted(sizes.items())))(jax.random.key(seed))


@functools.lru_cache(maxsize=None)
def _seeded(sizes: tuple):
    def made(key):
        params = ref.init(key, dict(sizes))
        moved = lambda k, v: v + 0.1 * jax.random.normal(jax.random.key(7), v.shape) if k.split(".")[-1] in STARTS_AT_ONE else v
        return {k: ROUTER_SPREAD * v if k.endswith("router") else moved(k, v) for k, v in params.items()}

    return jax.jit(made)


def tokens_of(seed: int, vocab: int, rows: int = ROWS, length: int = LENGTH):
    return jax.random.randint(jax.random.key(seed), (rows, length + 1), 0, vocab)


def rel(a, b, floor: float = 1e-30) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), floor))


# -- (a) YaRN, the rotary embedding and the router: the new arguments, and the defaults as they were -----------

@pytest.mark.parametrize("dim,theta,factor,original,fast,slow", [
    (64, 5e5, 128.0, 8192, 32.0, 1.0),      # laguna_s21's full layers: the ramp between pairs 9 and 18
    (64, 1e4, 16.0, 2, 32.0, 1.0),          # both ends of the range below 0: clamped to 0, the same: no ramp
    (32, 10.0, 4.0, 10**9, 32.0, 1.0),      # both ends past the last dimension: clamped to dim - 1
    (8, 500.0, 8.0, 64, 8.0, 1.0),          # the toy model's
], ids=["laguna", "clamped_low", "clamped_high", "toy"])
def test_yarn_frequencies_match_the_transcription_of_the_formula(dim, theta, factor, original, fast, slow):
    got = yarn_inv_freq(dim, theta, factor, original, fast, slow)
    want = ref.yarn_inv_freq(dim, theta, factor, original, fast, slow)
    assert got.shape == (dim // 2,) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-6)
    own = theta ** (-np.arange(0, dim, 2) / dim)
    if dim == 64 and theta == 5e5:
        np.testing.assert_allclose(got[:10], own[:10], rtol=2e-6)            # below the range: the pair's own
        np.testing.assert_allclose(got[18:], own[18:] / factor, rtol=2e-6)   # above it: factor times slower
        assert np.all((got[10:18] < own[10:18]) & (got[10:18] > own[10:18] / factor))


def _rotary_before(x, rotary_dim: int, theta: float, first_position: int = 0):
    """`partial_rotary` as it was before it took YaRN's arguments."""
    length, half = x.shape[1], rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (first_position + jnp.arange(length, dtype=jnp.float32))[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = jnp.split(x.astype(jnp.float32), (half, rotary_dim), axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1).astype(x.dtype)


def _route_before(logits, k: int):
    """`moe.softmax_topk_route` as it was before it took a scale."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, idx = jax.lax.top_k(probs, k)
    chosen = moe._chosen(probs, idx)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_partial_rotary_with_its_defaults_is_todays_arithmetic_and_the_attention_factor_scales_the_turned_part(dtype):
    x = jax.random.normal(jax.random.key(0), (2, 12, 3, 16)).astype(dtype)
    got = jax.jit(lambda x: partial_rotary(x, 8, 1e4, 3))(x)
    np.testing.assert_array_equal(got, jax.jit(lambda x: _rotary_before(x, 8, 1e4, 3))(x))
    inv = np.asarray(1e4 ** (-jnp.arange(4, dtype=jnp.float32) / 4))
    scaled = partial_rotary(x, 8, 1e4, 3, inv_freq=inv, attention_factor=1.5)
    np.testing.assert_allclose(np.asarray(scaled[..., :8], np.float32), 1.5 * np.asarray(got[..., :8], np.float32),
                               rtol=1e-5 if dtype == jnp.float32 else 1e-2, atol=1e-5)
    np.testing.assert_array_equal(scaled[..., 8:], x[..., 8:])


def test_softmax_router_at_scale_one_is_todays_and_a_scale_multiplies_the_weights():
    logits = jax.random.normal(jax.random.key(1), (40, 16))
    got_idx, got = jax.jit(lambda l: moe.softmax_topk_route(l, 3))(logits)
    idx, before = jax.jit(lambda l: _route_before(l, 3))(logits)
    np.testing.assert_array_equal(got_idx, idx)
    np.testing.assert_array_equal(got, before)
    np.testing.assert_allclose(moe.softmax_topk_route(logits, 3, 2.5)[1], 2.5 * before, rtol=1e-6)
    dense = jnp.zeros_like(logits).at[jnp.arange(40)[:, None], idx].set(2.5 * before)
    np.testing.assert_allclose(dense, ref.route({"router": jnp.eye(16)}, logits, {"top_k": 3, "routed_scale": 2.5}),
                               rtol=1e-5, atol=1e-7)


# -- (b) the share and the model: what the shares give adds up to the uncut expert block ----------------------

def test_the_shares_of_an_expert_block_add_up_to_the_uncut_reference():
    """16 experts over 2 shares: the routed parts all the shares give, with the router, the shared expert and
    its gate, which every chip computes alike, counted once, add up to the uncut reference's block."""
    from distribuuuu_tpu.models import qwen3_next

    m = lg()
    share = dict(FULL, experts_held=8)
    ways = FULL["experts"] // share["experts_held"]
    params = {k.split(".")[1]: v for k, v in seeded(dict(FULL, pattern="W")).items() if k.startswith("L0.")}
    x = jax.random.normal(jax.random.key(2), (ROWS, LENGTH, FULL["dim"]))
    want = ref.experts(params, x, FULL)
    shared = ref.shared_expert(params, x.reshape(-1, FULL["dim"])).reshape(x.shape)
    total, loads = 0.0, []
    for rank in range(ways):
        held = slice(rank * share["experts_held"], (rank + 1) * share["experts_held"])
        sizes = m.Sizes(pattern="W", **dict(share, expert_first=held.start))
        p = dict(params, w1=params["w1"][held], w2=params["w2"][held])
        block = lambda p, x, sizes=sizes: qwen3_next.expert_block(p, x, sizes, jnp.float32, routed_scale=sizes.routed_scale)
        out, counts = jax.jit(block)(p, x)
        total = total + out
        loads.append(counts)
    total = total - (ways - 1) * shared  # every rank added the whole shared expert: count it once
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-6)
    assert int(sum(jnp.sum(c) for c in loads)) == ROWS * LENGTH * FULL["top_k"]  # every slot landed on one share


# -- (c) program against reference: loss, every gradient leaf, the checkpoint, two optimizer steps -------------

@functools.lru_cache(maxsize=None)
def _program_grads(pattern: str, dtype, remat: bool):
    """The program's loss and gradient as one jitted function of its tree and the tokens, once a model for the
    file's cases (a compile is most of a case's time)."""
    model = model_of(pattern, SHARE, dtype, remat)
    return model, jax.jit(jax.value_and_grad(lambda p, tokens: trainer._forward_loss_lm(model, p, {}, {"tokens": tokens})[0]))


@functools.lru_cache(maxsize=None)
def _reference_grads(pattern: str):
    sizes = dict(SHARE, pattern=pattern)
    return jax.jit(jax.value_and_grad(lambda p, tokens: ref.loss_fn(p, {}, tokens, sizes)))


def _program_loss_and_grads(pattern: str, dtype, remat: bool, params: dict, tokens):
    model, grads = _program_grads(pattern, dtype, remat)
    return grads(to_program(params, model), tokens)


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [(jnp.float32, 2e-6, 2e-4), (jnp.bfloat16, 2e-3, 6e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", ["DWWWF", "DWF"], ids=["scanned", "unscanned"])
def test_loss_and_every_gradient_leaf_match_the_reference(fresh_cfg, pattern, dtype, loss_tol, grad_tol):
    """float32 tight (the two differ in the order of sums: the program's window core takes its keys in blocks,
    the reference's dense); bfloat16 at the tolerance its 8 bits of mantissa in every product's operands give:
    6e-2 on every leaf but the routers' and the norms before them (`ROUTING_BF16`). A leaf's gap is taken over its
    own norm or a hundredth of the median leaf's, whichever is larger: the router's leaves have gradients far
    under a matrix's."""
    fresh_cfg.LM.LOSS_BLOCK = 8
    model = model_of(pattern, SHARE, dtype)
    params = seeded(dict(SHARE, pattern=pattern))
    tokens = tokens_of(5, SHARE["vocab"])
    want_loss, want = _reference_grads(pattern)(params, tokens)
    got_loss, got = _program_loss_and_grads(pattern, dtype, True, params, tokens)
    assert abs(float(got_loss) - float(want_loss)) <= loss_tol * abs(float(want_loss))
    want_tree = to_program(want, model)
    assert set(got) == set(want_tree)
    floor = 1e-2 * statistics.median(float(jnp.linalg.norm(v)) for v in want_tree.values())
    for name in want_tree:
        routing = name.endswith(("router", "post_norm")) and dtype == jnp.bfloat16
        assert rel(got[name], want_tree[name], floor) <= (ROUTING_BF16 if routing else grad_tol), name


def test_remat_under_the_policy_computes_what_no_remat_computes(fresh_cfg):
    """A checkpoint chooses what is stored and what is computed again, and adds no cast: float32, the same
    arithmetic, so the loss and every gradient leaf agree to rounding of the sums' order."""
    fresh_cfg.LM.LOSS_BLOCK = 8
    pattern = "DWWWF"
    params = seeded(dict(SHARE, pattern=pattern))
    tokens = tokens_of(5, SHARE["vocab"])
    want_loss, want = _program_loss_and_grads(pattern, jnp.float32, False, params, tokens)
    got_loss, got = _program_loss_and_grads(pattern, jnp.float32, True, params, tokens)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert set(got) == set(want)
    floor = 1e-2 * statistics.median(float(jnp.linalg.norm(v)) for v in want.values())
    for name in want:
        assert rel(got[name], want[name], floor) <= 1e-6, name


def test_two_adafactor_steps_through_the_trainer_match_the_reference(fresh_cfg, monkeypatch):
    """The jitted train step as the trainer builds it (task lm, guard, donated state), float32, under the
    cell's optimizer, against the reference's loss and gradients under Adafactor's plain formulas; the
    routing counters ride the metrics."""
    from distribuuuu_tpu import obs

    cfg = fresh_cfg
    monkeypatch.setattr(optim, "FACTOR_MIN_DIM", 16)  # so that the toy's matrices are factored, as the cell's are
    cfg.TRAIN.TASK, cfg.OPTIM.OPTIMIZER, cfg.LM.LOSS_BLOCK, cfg.OPTIM.WEIGHT_DECAY = "lm", "adafactor", 8, 0.0
    pattern = "DWWWF"
    sizes = dict(SHARE, pattern=pattern)
    model = model_of(pattern, SHARE)
    mesh = data_mesh(1)
    state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
    assert state.batch_stats == {}  # a softmax router has no buffer
    params = seeded(sizes, seed=4)
    # the step donates its state; placed as its own outputs are, so that the second step reuses the first's program
    placed = NamedSharding(mesh, PartitionSpec())
    state = state.replace(params=jax.device_put(jax.tree.map(jnp.copy, to_program(params, model)), placed))
    step = trainer.make_train_step(model, tx, mesh, topk=5)
    ref_params = to_program(params, model)  # the reference follows in the program's leaves
    zeros = lambda p: ((jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-1])),
                        jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-2])))
                       if p.ndim >= 2 and sorted(p.shape)[-2] >= 16 else (jnp.zeros_like(p),))
    ref_state = {"t": 0, "v": {k: zeros(p) for k, p in ref_params.items()}}
    flat = params
    ref_grads = _reference_grads(pattern)
    ref_step = jax.jit(lambda p, g, st: _token_layers.adafactor(p, to_program(g, model), st, 0.01, 16))
    for i in range(2):
        tokens = tokens_of(10 + i, SHARE["vocab"])
        state, metrics = step(state, {"tokens": tokens}, jnp.float32(0.01), jax.random.key(1))
        loss, grads = ref_grads(flat, tokens)
        ref_params, ref_state = ref_step(ref_params, grads, ref_state)
        flat = _token_layers.from_program(ref_params, model.sizes.pattern)
        assert float(metrics["loss_sum"] / metrics["n"]) == pytest.approx(float(loss), rel=2e-5)
        assert set(obs.WINDOW_COUNTERS) <= set(metrics)
    # Adafactor divides a gradient by its own size: where the true gradient is zero and what is computed is
    # rounding (a router's column of an expert that no token chose beside a held one), the update is the
    # rounding's sign at full size, in the program and in the reference alike, so such a leaf may differ by what
    # two steps can move it (3 %), and the experts' leaves, routed on step two by it, by some parts in a thousand
    for name, value in ref_params.items():
        assert rel(state.params[name], value) <= (3e-2 if name.endswith("router") else 5e-3), name


# -- (d) the factory and the shipped configuration -------------------------------------------------------------

def test_shipped_yaml_builds_the_configurations_811m_parameters(fresh_cfg):
    """Shapes only: the published widths with the held shares count 811,030,784 parameters, the heads of each
    kind as published."""
    from distribuuuu_tpu import config

    config.cfg.merge_from_file(os.path.join(os.path.dirname(HERE), "config", "laguna_s21.yaml"))
    model = trainer._build_cfg_model()
    shapes = lg().param_shapes(model.sizes)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 811_030_784
    assert shapes["L0_q"] == (3072, 48 * 128) and shapes["L0_ff1"] == (3072, 2 * 12288)
    assert shapes["U0_q"] == (3, 3072, 72 * 128) and shapes["U0_gate"] == (3, 3072, 72)
    assert shapes["L4_q"] == (3072, 48 * 128) and shapes["L4_k"] == (3072, 8 * 128)
    assert shapes["U0_w1"] == (3, 8, 3072, 2048) and shapes["embed"] == (12544, 3072)
    s = model.sizes
    assert (s.window, s.top_k, s.routed_scale, s.yarn_factor, s.yarn_original_positions) == (512, 10, 2.5, 128.0, 8192)
    assert math.isclose(s.yarn_attention_factor, 1.4852030263919618) and s.rope_share == 0.5
    assert model.remat and model.dtype == jnp.bfloat16
