"""The second token model (models/qwen3_next.py) and what it brought, against the plain reference
``tests/reference/qwen3_next.py``: the chunked gated delta rule against the recurrence a position at a
time; partial rotary attention; the softmax router; `held_experts` with a gated expert between its
products; loss, every gradient leaf and three optimizer steps through the trainer; the layer
checkpoint; the shares of an expert block add up to the uncut block; the new scope reaches the compiled
step. CPU, toy widths, seeded weights."""

from __future__ import annotations

import functools
import importlib.util
import os
import re
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import obs, optim, trainer
from distribuuuu_tpu.runtime import data_mesh

import _token_layers

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(HERE, "reference", "qwen3_next.py"), "reference_qwen3_next")

# toy widths; the counts are the *uncut* block's, of which the share test takes parts
FULL = dict(vocab=48, dim=32, linear_key_heads=2, linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
            conv_kernel=4, chunk=16, attn_heads=4, kv_heads=2, head_dim=16, rope_share=0.25, rope_theta=1e4,
            experts=16, experts_held=16, expert_first=0, top_k=3, expert_width=24, shared_width=24, eps=1e-6)
SHARE = dict(FULL, experts_held=4, expert_first=4)  # one chip's share of it: a quarter of the experts
ROWS, LENGTH = 2, 24  # no multiple of the chunk
PATTERNS = ["G", "A", "GGGA"]
STARTS_FLAT = ("norm", "post_norm", "q_norm", "k_norm", "gnorm", "dt_bias", "norm_f")  # leaves that start at 0 or 1


def qn():
    """The program's module, imported when a test asks: it registers an arch, and ``list_models()`` is
    a parametrisation of other files."""
    from distribuuuu_tpu.models import qwen3_next

    return qwen3_next


def model_of(pattern: str, sizes: dict, dtype=jnp.float32, remat: bool = True):
    m = qn()
    return m.Qwen3Next(m.Sizes(pattern=pattern, **sizes), dtype=dtype, remat=remat)


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

    return {name: leaf(name) for name in qn().param_shapes(model.sizes)}


def seeded(sizes: dict, seed: int = 3) -> dict:
    """The reference's weights, with the leaves that start at 0 or 1 moved off them so that each counts."""
    params = ref.init(jax.random.key(seed), sizes)
    return {k: v + 0.1 * jax.random.normal(jax.random.key(7), v.shape) if k.split(".")[-1] in STARTS_FLAT else v
            for k, v in params.items()}


def tokens_of(seed: int, vocab: int, rows: int = ROWS, length: int = LENGTH):
    return jax.random.randint(jax.random.key(seed), (rows, length + 1), 0, vocab)


def rel(a, b, floor: float = 1e-30) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), floor))


# -- (a) the chunked gated delta rule against the recurrence a position at a time ------------------------

def _rule_inputs(length: int, seed: int = 0, b=2, h=3, kd=8, vd=6):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, length, h, kd))) * kd ** -0.5
    k = unit(jax.random.normal(ks[1], (b, length, h, kd)))
    v = jax.random.normal(ks[2], (b, length, h, vd))
    g = -jnp.exp(2.0 * jax.random.normal(ks[3], (b, length, h)))  # decays from next to nothing to next to 1
    beta = jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (b, length, h)))
    return q, k, v, g, beta


REGIMES = {
    "as_drawn": lambda g, beta: (g, beta),
    "alpha_near_0": lambda g, beta: (jnp.full_like(g, -30.0), beta),
    "alpha_near_1": lambda g, beta: (jnp.full_like(g, -1e-4), beta),
    "alpha_1": lambda g, beta: (jnp.zeros_like(g), beta),
    "beta_0": lambda g, beta: (g, jnp.zeros_like(beta)),   # nothing is written: the state only decays, from zero
    "beta_1": lambda g, beta: (g, jnp.ones_like(beta)),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("length", [32, 40, 7], ids=["two_chunks", "not_a_multiple", "under_a_chunk"])
def test_chunked_delta_rule_matches_the_recurrence_forward_and_gradient(length, regime):
    from distribuuuu_tpu.ops.gdn import gated_delta_rule

    q, k, v, g, beta = _rule_inputs(length)
    g, beta = REGIMES[regime](g, beta)
    chunked = lambda q, k, v, g, beta: gated_delta_rule(q, k, v, g, beta, chunk=16)
    plain = lambda q, k, v, g, beta: ref.delta_rule(q, k, v, jnp.exp(g), beta)
    want, got = plain(q, k, v, g, beta), chunked(q, k, v, g, beta)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    if regime == "beta_0":
        assert float(jnp.max(jnp.abs(got))) == 0.0
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    for name, a, b in zip("q k v g beta".split(), grads(chunked), grads(plain)):
        assert rel(a, b, floor=1e-6) <= 2e-4, name


def test_chunked_delta_rule_carries_its_state_across_chunks():
    """A value written in the first chunk under no decay is read back, whole, by the same key three chunks on."""
    from distribuuuu_tpu.ops.gdn import gated_delta_rule

    length, kd, vd = 64, 8, 4
    k = jnp.zeros((1, length, 1, kd)).at[0, 0, 0, 0].set(1.0).at[0, 1:, 0, 1].set(1.0)  # key e0 once, then e1
    q = jnp.zeros((1, length, 1, kd)).at[0, 50, 0, 0].set(1.0)
    v = jnp.zeros((1, length, 1, vd)).at[0, 0, 0].set(jnp.arange(1.0, 1.0 + vd))
    out = gated_delta_rule(q, k, v, jnp.zeros((1, length, 1)), jnp.ones((1, length, 1)), chunk=16)
    np.testing.assert_allclose(out[0, 50, 0], jnp.arange(1.0, 1.0 + vd), rtol=1e-6)


@pytest.mark.parametrize("size", [16, 64])
def test_unit_lower_inverse_is_the_inverse(size):
    from distribuuuu_tpu.ops.gdn import unit_lower_inverse

    a = jnp.tril(0.3 * jax.random.normal(jax.random.key(0), (3, size, size)), -1)
    want = np.linalg.inv(np.eye(size) + np.asarray(a, np.float64))
    np.testing.assert_allclose(unit_lower_inverse(a), want, rtol=2e-4, atol=2e-5)


# -- (b) partial rotary attention, the softmax router, the gated expert --------------------------------

@pytest.mark.parametrize("length", [20, 5], ids=["blocks", "short"])
def test_gated_rotary_attention_matches_the_reference(length, monkeypatch):
    from distribuuuu_tpu.ops import attention

    monkeypatch.setattr(attention, "CAUSAL_BLOCK", 8)
    m = qn()
    s = m.Sizes(pattern="A", **SHARE)
    p = {k.split(".")[1]: v for k, v in seeded(dict(SHARE, pattern="A")).items() if k.startswith("L0.")}
    u = jax.random.normal(jax.random.key(1), (2, length, SHARE["dim"]))
    got, want = jax.jit(lambda p, u: m.attention_mixer(p, u, s))(p, u), ref.attention(p, u, SHARE)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_rotary_scores_depend_on_the_offset_alone_and_leave_the_rest_of_a_head():
    from distribuuuu_tpu.ops.attention import partial_rotary

    q, k = (jax.random.normal(jax.random.key(i), (1, 12, 2, 16)) for i in (0, 1))
    scores = lambda first: jnp.einsum("bqhd,bkhd->bhqk", partial_rotary(q, 8, 1e4, first), partial_rotary(k, 8, 1e4, first))
    np.testing.assert_allclose(scores(0), scores(37), rtol=1e-4, atol=1e-4)  # all positions shifted: equal offsets, equal scores
    assert not np.allclose(scores(0), jnp.einsum("bqhd,bkhd->bhqk", q, k), atol=1e-2)
    np.testing.assert_array_equal(partial_rotary(q, 8, 1e4, 5)[..., 8:], q[..., 8:])  # the other dimensions untouched
    np.testing.assert_allclose(partial_rotary(q, 8, 1e4), ref.rotary(q, 0.5, 1e4), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(partial_rotary(q, 8, 1e4)[:, 0], q[:, 0])  # position 0 turns nothing


def test_causal_attention_takes_the_rows_in_groups_where_a_blocks_scores_would_not_fit(monkeypatch):
    """The same numbers whether all rows go through a block at once or a group after the other."""
    from distribuuuu_tpu.ops import attention, rows

    qkv = jax.random.normal(jax.random.key(0), (4, 16, (4 + 2 * 2) * 8))
    whole = attention.xla_causal_attention(qkv, 4, 2, block=8)
    monkeypatch.setattr(rows, "GROUP_BYTES", 4 * 4 * 8 * 16 * 2)  # two rows' scores of a block
    grouped = jax.jit(lambda t: attention.xla_causal_attention(t, 4, 2, block=8))
    assert "while" in grouped.lower(qkv).as_text()
    np.testing.assert_allclose(grouped(qkv), whole, rtol=1e-6, atol=1e-7)
    grads = lambda f: jax.grad(lambda t: jnp.sum(jnp.sin(f(t))))(qkv)
    np.testing.assert_allclose(grads(grouped), grads(lambda t: attention.xla_causal_attention(t, 4, 2, block=8)),
                               rtol=1e-5, atol=1e-7)


def test_softmax_router_keeps_the_top_k_renormalised_ties_to_the_lower_id():
    from distribuuuu_tpu.parallel import moe

    logits = jnp.array([[2.0, 1.0, 0.0, -1.0, 1.0], [0.5, 0.5, 0.5, 0.5, 0.5], [0.0, 3.0, 3.0, -2.0, 1.0]])
    idx, w = moe.softmax_topk_route(logits, 2)
    assert [sorted(row) for row in idx.tolist()] == [[0, 1], [0, 1], [1, 2]]  # a tie goes to the lower id
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.0, rtol=1e-6)
    p = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_allclose(w[0, idx[0].tolist().index(0)], p[0, 0] / (p[0, 0] + p[0, 1]), rtol=1e-6)
    dense = jnp.zeros_like(logits).at[jnp.arange(3)[:, None], idx].set(w)
    np.testing.assert_allclose(dense, ref.route({"router": jnp.eye(5)}, logits, {"top_k": 2}), rtol=1e-6)
    # the gradient reaches the logits through the chosen probabilities and their sum, as the reference's does
    got = jax.grad(lambda l: jnp.sum(jnp.sin(moe.softmax_topk_route(l, 2)[1])))(logits)
    want = jax.grad(lambda l: jnp.sum(jnp.sin(jnp.take_along_axis(ref.route({"router": jnp.eye(5)}, l, {"top_k": 2}), idx, 1))))(logits)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


ROUTINGS = {
    "same_experts": lambda tokens, k: jnp.tile(jnp.array([[5, 4, 9]]), (tokens, 1)),  # two held, one absent
    "one_held_expert": lambda tokens, k: jnp.tile(jnp.array([[6, 1, 12]]), (tokens, 1)),  # all tokens on expert 6
    "none_held": lambda tokens, k: jnp.tile(jnp.array([[0, 1, 12]]), (tokens, 1)),
    "as_drawn": lambda tokens, k: jnp.argsort(jax.random.uniform(jax.random.key(9), (tokens, 16)), axis=-1)[:, :k],
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("round_rows,block", [(8, 4), (256, 256)], ids=["rounds", "roomy"])
def test_held_gated_experts_match_one_expert_at_a_time(round_rows, block, kernels, routing, monkeypatch):
    """`held_experts` with ``silu(gate) ⊙ up`` between its products, over a first product of twice the width,
    through XLA's batched products and through `ops/grouped.py`'s kernel pair in the interpreter."""
    from distribuuuu_tpu.parallel import moe

    monkeypatch.setattr(moe, "BLOCK", block)
    monkeypatch.setattr(moe, "_takes_the_kernels", lambda *_: kernels)
    tokens, held, k, first = 64, 4, 3, 4
    dim, width = (128, 128) if kernels else (16, 24)
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (tokens, dim))
    w1, w2 = 0.3 * jax.random.normal(ks[1], (held, dim, 2 * width)), 0.3 * jax.random.normal(ks[2], (held, width, dim))
    idx = ROUTINGS[routing](tokens, k)
    w = jax.nn.softmax(jax.random.normal(ks[3], (tokens, k)), axis=-1)

    def one_at_a_time(x, w, w1, w2):
        y = jnp.zeros_like(x)
        for e in range(held):
            gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
            y = y + (ref.silu_gated(x @ w1[e]) @ w2[e]) * gate[:, None]
        return y

    held_experts = functools.partial(moe.held_experts, between=moe.silu_gated)
    y, counts = jax.jit(lambda *a: held_experts(a[0], idx, *a[1:], first, round_rows))(x, w, w1, w2)
    assert counts.tolist() == [int(jnp.sum(idx == first + e)) for e in range(held)]
    np.testing.assert_allclose(y, one_at_a_time(x, w, w1, w2), rtol=2e-4, atol=2e-5)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3)))(x, w, w1, w2)
    for got, want in zip(grads(lambda *a: held_experts(a[0], idx, *a[1:], first, round_rows)[0]), grads(one_at_a_time)):
        assert rel(got, want, floor=1e-6) <= 2e-4


# -- (c) program against reference: loss, every gradient leaf, the checkpoint, three optimizer steps -----

def _program_loss_and_grads(model, tree, tokens):
    def loss(p):
        return trainer._forward_loss_lm(model, p, {}, {"tokens": tokens})[0]

    return jax.jit(jax.value_and_grad(loss))(tree)


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [(jnp.float32, 2e-6, 2e-4), (jnp.bfloat16, 2e-3, 6e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_loss_and_every_gradient_leaf_match_the_reference(fresh_cfg, pattern, dtype, loss_tol, grad_tol):
    """float32 tight (the two differ in the order of sums, and in the chunked form of the recurrence);
    bfloat16 at the tolerance its 8 bits of mantissa in every product's operands give. A leaf's gap is
    taken over its own norm or a hundredth of the median leaf's, whichever is larger: the decays' leaves
    (``a_log``, ``dt_bias``) have gradients a hundred thousand times smaller than a matrix's."""
    fresh_cfg.LM.LOSS_BLOCK = 16
    sizes = dict(SHARE, pattern=pattern)
    model = model_of(pattern, SHARE, dtype)
    params = seeded(sizes)
    tokens = tokens_of(5, SHARE["vocab"])
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: ref.loss_fn(p, {}, tokens, sizes)))(params)
    got_loss, got = _program_loss_and_grads(model, to_program(params, model), tokens)
    assert abs(float(got_loss) - float(want_loss)) <= loss_tol * abs(float(want_loss))
    want_tree = to_program(want, model)
    assert set(got) == set(want_tree)
    floor = 1e-2 * statistics.median(float(jnp.linalg.norm(v)) for v in want_tree.values())
    for name in want_tree:
        assert rel(got[name], want_tree[name], floor) <= grad_tol, name


@pytest.mark.parametrize("pattern", ["GGGA", "GA", "GG"], ids=["scanned_then_attention", "unscanned", "scanned"])
def test_remat_under_the_policy_computes_what_no_remat_computes(fresh_cfg, pattern):
    """A checkpoint chooses what is stored and what is computed again, and adds no cast: float32, the same
    arithmetic, so the loss and every gradient leaf agree to rounding of the sums' order."""
    fresh_cfg.LM.LOSS_BLOCK = 16
    params = seeded(dict(SHARE, pattern=pattern))
    tokens = tokens_of(5, SHARE["vocab"])
    plain, remat = model_of(pattern, SHARE, remat=False), model_of(pattern, SHARE, remat=True)
    want_loss, want = _program_loss_and_grads(plain, to_program(params, plain), tokens)
    got_loss, got = _program_loss_and_grads(remat, to_program(params, remat), tokens)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert set(got) == set(want)
    floor = 1e-2 * statistics.median(float(jnp.linalg.norm(v)) for v in want.values())
    for name in want:
        assert rel(got[name], want[name], floor) <= 1e-6 if name.split("_", 1)[-1] not in ("a_log", "dt_bias") \
            else rel(got[name], want[name], floor) <= 1e-5, name


def _adafactor(params, grads, state, lr, min_dim):
    """Adafactor's plain formulas, as `optim.py` composes them (no first moment, decay ``1 - t^-0.8``, the update
    clipped to unit root-mean-square and scaled by the leaf's own, at least 1e-3)."""
    decay = 1.0 - (state["t"] + 1.0) ** -0.8
    rms = lambda t: jnp.sqrt(jnp.mean(t ** 2))
    out, new = {}, {}
    for k, p in params.items():
        g, sq = grads[k], grads[k] ** 2 + 1e-30
        order = np.argsort(p.shape)
        if p.ndim >= 2 and p.shape[order[-2]] >= min_dim:
            d1, d0 = int(order[-2]), int(order[-1])
            row = decay * state["v"][k][0] + (1 - decay) * jnp.mean(sq, axis=d0)
            col = decay * state["v"][k][1] + (1 - decay) * jnp.mean(sq, axis=d1)
            new[k] = (row, col)
            row_mean = jnp.mean(row, axis=d1 - 1 if d1 > d0 else d1, keepdims=True)
            u = g * jnp.expand_dims((row / row_mean) ** -0.5, d0) * jnp.expand_dims(col ** -0.5, d1)
        else:
            new[k] = (decay * state["v"][k][0] + (1 - decay) * sq,)
            u = g * new[k][0] ** -0.5
        u = u / jnp.maximum(1.0, rms(u))
        out[k] = p - lr * u * jnp.maximum(rms(p), 1e-3)
    return out, {"t": state["t"] + 1, "v": new}


@pytest.mark.parametrize("pattern", PATTERNS)
def test_three_adafactor_steps_through_the_trainer_match_the_reference(fresh_cfg, pattern, monkeypatch):
    """The jitted train step as the trainer builds it (task lm, guard, donated state), float32, under the
    cell's optimizer, against the reference's loss and gradients under Adafactor's plain formulas; the
    routing counters ride the metrics."""
    cfg = fresh_cfg
    monkeypatch.setattr(optim, "FACTOR_MIN_DIM", 16)  # so that the toy's matrices are factored, as the cell's are
    cfg.TRAIN.TASK, cfg.OPTIM.OPTIMIZER, cfg.LM.LOSS_BLOCK, cfg.OPTIM.WEIGHT_DECAY = "lm", "adafactor", 16, 0.0
    sizes = dict(SHARE, pattern=pattern)
    model = model_of(pattern, SHARE)
    mesh = data_mesh(1)
    state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
    assert state.batch_stats == {}  # a softmax router has no buffer
    params = seeded(sizes, seed=4)
    state = state.replace(params=jax.tree.map(jnp.copy, to_program(params, model)))  # the step donates its state
    step = trainer.make_train_step(model, tx, mesh, topk=5)
    ref_params = to_program(params, model)  # the reference follows in the program's leaves
    zeros = lambda p: ((jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-1])),
                        jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-2])))
                       if p.ndim >= 2 and sorted(p.shape)[-2] >= 16 else (jnp.zeros_like(p),))
    ref_state = {"t": 0, "v": {k: zeros(p) for k, p in ref_params.items()}}
    flat = params
    ref_grads = jax.jit(jax.value_and_grad(lambda p, tokens: ref.loss_fn(p, {}, tokens, sizes)))
    for i in range(3):
        tokens = tokens_of(10 + i, SHARE["vocab"])
        state, metrics = step(state, {"tokens": tokens}, jnp.float32(0.01), jax.random.key(1))
        loss, grads = ref_grads(flat, tokens)
        ref_params, ref_state = _adafactor(ref_params, to_program(grads, model), ref_state, 0.01, 16)
        flat = _token_layers.from_program(ref_params, model.sizes.pattern)
        assert float(metrics["loss_sum"] / metrics["n"]) == pytest.approx(float(loss), rel=2e-5)
        assert set(obs.WINDOW_COUNTERS) <= set(metrics)
    # Adafactor divides a gradient by its own size, entry by entry or row and column: where the true gradient is
    # zero and what is computed is rounding (a router's column of an expert that no token chose beside a held one, at
    # 50 tokens; the decays' leaves and the projection that feeds them, a hundred thousand times under a matrix's),
    # the update is the rounding's sign at full size, in the program and in the reference alike, so such a leaf may
    # differ by what three steps can move it (3 %); and a router that differs so routes steps two and three with
    # other weights, which the experts' leaves then show (some parts in a thousand)
    loose = ("router", "in_ba", "a_log", "dt_bias")
    for name, value in ref_params.items():
        assert rel(state.params[name], value) <= (3e-2 if name.split("_", 1)[-1] in loose else 5e-3), name


# -- (d) the share and the model: what the shares give adds up to the uncut expert block ----------------

def test_the_shares_of_an_expert_block_add_up_to_the_uncut_reference():
    """16 experts over 4 shares: the routed parts all the shares give, with the router, the shared expert and
    its gate, which every chip computes alike, counted once, add up to the uncut reference's block."""
    m = qn()
    ways = FULL["experts"] // SHARE["experts_held"]
    params = {k.split(".")[1]: v for k, v in seeded(dict(FULL, pattern="G")).items() if k.startswith("L0.")}
    h = jax.random.normal(jax.random.key(2), (ROWS, LENGTH, FULL["dim"]))
    x = ref.rms_norm(h, params["post_norm"], FULL["eps"])
    want = ref.experts(params, x, FULL)
    flat = x.reshape(-1, FULL["dim"])
    shared = (jax.nn.sigmoid(ref.mm(flat, params["shared_gate"][:, None]))
              * ref.mm(ref.silu_gated(ref.mm(flat, params["shared1"])), params["shared2"])).reshape(h.shape)
    total, loads = 0.0, []
    for rank in range(ways):
        held = slice(rank * SHARE["experts_held"], (rank + 1) * SHARE["experts_held"])
        sizes = m.Sizes(pattern="G", **dict(SHARE, expert_first=held.start))
        p = dict(params, w1=params["w1"][held], w2=params["w2"][held])
        out, counts = jax.jit(lambda p, x, sizes=sizes: m.expert_block(p, x, sizes, jnp.float32))(p, x)
        total = total + out
        loads.append(counts)
    total = total - (ways - 1) * shared  # every rank added the whole shared expert: count it once
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-6)
    assert int(sum(jnp.sum(c) for c in loads)) == ROWS * LENGTH * FULL["top_k"]  # every slot landed on one share


# -- (e) the scopes, the factory, the shipped configuration ---------------------------------------------

@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


_COMPILED_NAMES: list = []  # the compiled step's op_names, once for the file's cases


def _compiled_names(cfg) -> list[str]:
    if not _COMPILED_NAMES:
        cfg.TRAIN.TASK, cfg.OPTIM.OPTIMIZER, cfg.LM.LOSS_BLOCK = "lm", "adafactor", 16
        model = model_of("GA", SHARE)
        mesh = data_mesh(1)
        state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
        step = trainer.make_train_step(model, tx, mesh, topk=5)
        batch = {"tokens": tokens_of(0, SHARE["vocab"])}
        text = step.lower(state, batch, jnp.float32(0.1), jax.random.key(1)).compile().as_text()
        _COMPILED_NAMES.extend(re.findall(r'op_name="([^"]*)"', text))
    return _COMPILED_NAMES


@pytest.mark.parametrize("scope", ["gdn_scan", "causal_attn", "mixer_proj", "dense_ffn", "moe_route", "moe_experts",
                                   "lm_head", "short_conv"])
def test_compiled_step_names_the_model_scopes_in_both_passes(fresh_cfg, no_compile_cache, scope):
    from distribuuuu_tpu.obs import trace as obs_trace

    assert scope in obs_trace.MODEL_SCOPES
    under = [n for n in _compiled_names(fresh_cfg) if f"/dtpu.{scope}/" in n]
    assert any("transpose(" in n for n in under), f"no backward op under dtpu.{scope}"
    assert any("transpose(" not in n for n in under), f"no forward op under dtpu.{scope}"


def test_lowered_step_holds_the_short_convolution_and_no_padded_copy_of_its_input(fresh_cfg):
    """The delta-net mixer's convolution is `ops.short_conv.causal_conv_silu` under its scope, and nothing in the
    step pads a ``[rows, length, q | k | v]`` tensor at its start: the taps read windows of the input itself
    (a pad that moves a gradient earlier, in the op's backward pass, widens nothing at the start)."""
    fresh_cfg.TRAIN.TASK, fresh_cfg.OPTIM.OPTIMIZER, fresh_cfg.LM.LOSS_BLOCK = "lm", "adafactor", 16
    model = model_of("GA", SHARE)
    mesh = data_mesh(1)
    state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
    step = trainer.make_train_step(model, tx, mesh, topk=5)
    text = step.lower(state, {"tokens": tokens_of(0, SHARE["vocab"])}, jnp.float32(0.1),
                      jax.random.key(1)).as_text(debug_info=True)
    assert "dtpu.short_conv" in text
    width = 2 * SHARE["linear_key_heads"] * SHARE["linear_key_dim"] + SHARE["linear_value_heads"] * SHARE["linear_value_dim"]
    starts = re.findall(rf"stablehlo\.pad .*low = \[0, (-?\d+), 0\].*\(tensor<{ROWS}x{LENGTH}x{width}x", text)
    assert starts and all(int(s) <= 0 for s in starts), starts


@pytest.mark.parametrize("arch,module,leaf", [("qwen3_next", "distribuuuu_tpu.models.qwen3_next", "U0_in_qkvz"),
                                              ("nemotron_h", "distribuuuu_tpu.models.nemotron_h", "U1_in_proj")])
def test_a_family_takes_the_keys_of_the_section_that_it_names(fresh_cfg, arch, module, leaf):
    """One ``LM`` section for both families: each factory builds from the keys its ``Sizes`` names, whatever
    else the section holds; the trainer tests no model's name."""
    cfg = fresh_cfg
    cfg.MODEL.ARCH, cfg.MODEL.MODULE, cfg.TRAIN.TASK = arch, module, "lm"
    cfg.LM.PATTERN = "GGGA" if arch == "qwen3_next" else "EMEM*"
    cfg.LM.VOCAB, cfg.LM.DIM, cfg.LM.EXPERTS, cfg.LM.EXPERTS_HELD, cfg.LM.EXPERT_WIDTH, cfg.LM.SHARED_WIDTH = 64, 32, 16, 4, 24, 24
    cfg.LM.LATENT, cfg.LM.MAMBA_HEADS, cfg.LM.MAMBA_HEAD_DIM, cfg.LM.SSM_STATE = 16, 4, 8, 16
    cfg.LM.LINEAR_KEY_HEADS, cfg.LM.LINEAR_VALUE_HEADS, cfg.LM.LINEAR_KEY_DIM, cfg.LM.LINEAR_VALUE_DIM = 2, 4, 8, 8
    cfg.LM.ATTN_HEADS, cfg.LM.KV_HEADS, cfg.LM.HEAD_DIM, cfg.LM.TOP_K = 4, 2, 16, 3
    model = trainer._build_cfg_model()
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), model.dummy_input(0)))["params"]
    assert leaf in shapes and shapes["embed"].shape == (64, 32)
    assert model.sizes.eps == cfg.LM.NORM_EPS and model.sizes.experts_held == 4


def test_shipped_yaml_builds_the_configurations_626m_parameters(fresh_cfg):
    """Shapes only: the published widths with the held shares count 625.7 M parameters."""
    from distribuuuu_tpu import config

    config.cfg.merge_from_file(os.path.join(os.path.dirname(HERE), "config", "qwen3_next.yaml"))
    model = trainer._build_cfg_model()
    shapes = qn().param_shapes(model.sizes)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 625_667_136
    assert shapes["U0_in_qkvz"] == (3, 2048, 12288) and shapes["L3_q"] == (2048, 8192)
    assert shapes["U0_w1"] == (3, 32, 2048, 1024) and shapes["embed"] == (18992, 2048)
    assert model.remat and model.dtype == jnp.bfloat16 and model.sizes.chunk == 64


def test_delta_rule_takes_the_rows_in_groups_where_the_chunks_tensors_would_not_fit(monkeypatch):
    """The same numbers whether all rows go through at once or a group after the other."""
    from distribuuuu_tpu.ops import gdn, rows

    q, k, v, g, beta = _rule_inputs(40, b=4)
    rule = lambda *a: gdn.gated_delta_rule(*a, chunk=16)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    whole, whole_grads = rule(q, k, v, g, beta), grads(rule)
    monkeypatch.setattr(rows, "GROUP_BYTES", 2 * gdn.CHUNK_TENSORS * 4 * 3 * 16 * 48)  # two rows' chunks' tensors
    grouped = jax.jit(rule)
    assert grouped.lower(q, k, v, g, beta).as_text().count("stablehlo.while") == 2  # the groups, and in them the chunks
    np.testing.assert_allclose(grouped(q, k, v, g, beta), whole, rtol=1e-6, atol=1e-7)
    for a, b in zip(grads(grouped), whole_grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
