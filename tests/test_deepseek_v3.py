"""The third token model (models/deepseek_v3.py) and what it brought, against the plain reference
``tests/reference/deepseek_v3.py``: the causal core of latent attention (keys wider than values, a rotary
key that all heads share) against the whole-head form; the latent algebra a decoder will need; a stack that
takes layers before its repeats; the sigmoid router over gated held experts; loss, every gradient leaf and
three optimizer steps through the trainer; the layer checkpoint; the shares of an expert block add up to the
uncut block; the new scope reaches the compiled step. CPU, toy widths, seeded weights."""

from __future__ import annotations

import importlib.util
import os
import re
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import obs, optim, trainer
from distribuuuu_tpu.models import token_lm
from distribuuuu_tpu.runtime import data_mesh

import _token_layers

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(HERE, "reference", "deepseek_v3.py"), "reference_deepseek_v3")

# toy widths, the key wider than the value as in the model; the counts are the *uncut* block's, of which the
# share test takes parts
FULL = dict(vocab=48, dim=32, attn_heads=4, kv_latent=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=6, rope_theta=1e4,
            dense_width=40, experts=16, experts_held=16, expert_first=0, top_k=3, expert_width=24, shared_width=48,
            routed_scale=2.448, eps=1e-6)
SHARE = dict(FULL, experts_held=4, expert_first=4)  # one chip's share of it: a quarter of the experts
ROWS, LENGTH = 2, 24
PATTERNS = ["D", "E", "DEE", "DEEEE"]
NORMS = ("norm", "post_norm", "kv_norm", "norm_f")  # leaves that start at 1


def dv3():
    """The program's module, imported when a test asks: it registers an arch, and ``list_models()`` is
    a parametrisation of other files."""
    from distribuuuu_tpu.models import deepseek_v3

    return deepseek_v3


def model_of(pattern: str, sizes: dict, dtype=jnp.float32, remat: bool = True):
    m = dv3()
    return m.DeepseekV3(m.Sizes(pattern=pattern, **sizes), dtype=dtype, remat=remat)


def to_program(flat: dict, pattern: str) -> dict:
    """The reference's per-layer leaves (``L2.w1``, ``L2.b_corr``) in the program's flat tree (``U0_w1 [repeats, ...]``)."""
    out = {}
    for prefix, where in _token_layers.layers_of(pattern).items():
        layers = where if isinstance(where, list) else [where]
        for short in {k.split(".", 1)[1] for k in flat if k.startswith(f"L{layers[0]}.")}:
            leaves = [flat[f"L{i}.{short}"] for i in layers]
            out[f"{prefix}_{short}"] = jnp.stack(leaves) if isinstance(where, list) else leaves[0]
    out.update({k: v for k, v in flat.items() if "." not in k})
    return out


def seeded(sizes: dict, seed: int = 3) -> tuple[dict, dict]:
    """The reference's weights, the norms' moved off 1 so that each counts, and correction buffers off 0 so that
    the choice is not the scores' own."""
    params = ref.init(jax.random.key(seed), sizes)
    params = {k: v + 0.1 * jax.random.normal(jax.random.key(7), v.shape) if k.split(".")[-1] in NORMS else v
              for k, v in params.items()}
    stats = {k: 0.05 * jax.random.normal(jax.random.key(11), v.shape) for k, v in ref.init_stats(sizes).items()}
    return params, stats


def tokens_of(seed: int, vocab: int, rows: int = ROWS, length: int = LENGTH):
    return jax.random.randint(jax.random.key(seed), (rows, length + 1), 0, vocab)


def rel(a, b, floor: float = 1e-30) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), floor))


# -- (a) the causal core of latent attention against the whole-head form --------------------------------

def _core_inputs(rows: int, length: int, heads=4, dk=8, dr=4, dv=6, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (rows, length, heads, dk + dr)), jax.random.normal(ks[1], (rows, length, heads, dk)),
            jax.random.normal(ks[2], (rows, length, dr)), jax.random.normal(ks[3], (rows, length, heads, dv)))


def _whole_head(q, k_own, k_shared, v):
    """The reference's form: the key whole a head, the shared part copied to each."""
    k = jnp.concatenate([k_own, jnp.broadcast_to(k_shared[:, :, None, :], (*k_own.shape[:-1], k_shared.shape[-1]))], axis=-1)
    out = ref.whole_head_attention(q, k, v)
    return out.reshape(*out.shape[:2], -1)


@pytest.mark.parametrize("grouped", [False, True], ids=["all_rows_at_once", "rows_in_groups"])
@pytest.mark.parametrize("rows", [1, 4], ids=["one_row", "four_rows"])
@pytest.mark.parametrize("length", [16, 20, 5], ids=["whole_blocks", "ragged_blocks", "under_a_block"])
def test_latent_core_matches_the_whole_head_form_forward_and_gradient(length, rows, grouped, monkeypatch):
    from distribuuuu_tpu.ops import attention
    from distribuuuu_tpu.ops import rows as rows_module

    args = _core_inputs(rows, length)
    if grouped:  # one row's scores of a block and no more: a group is a row
        monkeypatch.setattr(rows_module, "GROUP_BYTES", 4 * 4 * min(8, length) * length)
    core = jax.jit(lambda q, k_own, k_shared, v: attention.xla_causal_core(q, k_own, v, k_shared, block=8).reshape(rows, length, -1))
    assert ("while" in core.lower(*args).as_text()) is (grouped and rows > 1)
    got, want = core(*args), _whole_head(*args)
    assert got.shape == (rows, length, 4 * 6)  # heads x the value's width, not the key's
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3)))(*args)
    for name, a, b in zip(("q", "k_own", "k_shared", "v"), grads(core), grads(_whole_head)):
        assert rel(a, b, floor=1e-6) <= 2e-5, name


def test_latent_core_scales_by_the_whole_query_key_width():
    """The scale is that of the 192 (here 8 + 4), not of the part without position or of the value."""
    from distribuuuu_tpu.ops import attention

    q, k_own, k_shared, v = _core_inputs(1, 6)
    got = attention.latent_causal_attention(q, k_own, k_shared, v)

    def by_hand(scale):
        s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :8], k_own) + jnp.einsum("bqhd,bkd->bhqk", q[..., 8:], k_shared)) * scale
        w = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((6, 6), bool)), s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(1, 6, -1)

    np.testing.assert_allclose(got, by_hand(12 ** -0.5), rtol=2e-5, atol=2e-6)
    assert not np.allclose(got, by_hand(8 ** -0.5), atol=1e-3) and not np.allclose(got, by_hand(6 ** -0.5), atol=1e-3)


def test_packed_causal_attention_is_the_latent_core_with_equal_widths_and_no_shared_part():
    """One set of blocks under both entries: the packed grouped-query one, with every head its own key head,
    gives what the latent one gives for keys of the value's width and a shared part of no width."""
    from distribuuuu_tpu.ops import attention

    q, k, v = (jax.random.normal(jax.random.key(i), (2, 12, 4, 8)) for i in range(3))
    packed = attention.xla_causal_attention(jnp.concatenate([t.reshape(2, 12, 32) for t in (q, k, v)], axis=-1), 4, 4, block=8)
    latent = attention.xla_causal_core(q, k, v, jnp.zeros((2, 12, 0)), block=8).reshape(2, 12, -1)
    np.testing.assert_allclose(latent, packed, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("length", [20, 5])
def test_latent_attention_mixer_matches_the_reference(length):
    m = dv3()
    s = m.Sizes(pattern="D", **SHARE)
    p = {k.split(".")[1]: v for k, v in seeded(dict(SHARE, pattern="D"))[0].items() if k.startswith("L0.")}
    u = jax.random.normal(jax.random.key(1), (2, length, SHARE["dim"]))
    got, want = jax.jit(lambda p, u: m.latent_attention_mixer(p, u, s))(p, u), ref.attention(p, u, SHARE)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_shifting_every_position_leaves_the_scores_and_the_mixer_unchanged():
    """The rotary key is one head that every head reads: with all positions shifted, queries of every head against
    it give the scores of equal offsets, so causal attention gives the same output."""
    from distribuuuu_tpu.ops.attention import partial_rotary

    q_r = jax.random.normal(jax.random.key(0), (1, 12, 4, 4))
    k_r = jax.random.normal(jax.random.key(1), (1, 12, 1, 4))  # one head
    scores = lambda first: jnp.einsum("bqhd,bkd->bhqk", partial_rotary(q_r, 4, 1e4, first), partial_rotary(k_r, 4, 1e4, first)[:, :, 0])
    np.testing.assert_allclose(scores(0), scores(37), rtol=1e-4, atol=1e-4)
    assert not np.allclose(scores(0), jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0]), atol=1e-2)
    np.testing.assert_allclose(partial_rotary(q_r, 4, 1e4, 5), ref.rotary(q_r, 1e4, 5), rtol=1e-5, atol=1e-6)  # every dimension turns
    p = {k.split(".")[1]: v for k, v in seeded(dict(SHARE, pattern="D"))[0].items() if k.startswith("L0.")}
    u = jax.random.normal(jax.random.key(2), (2, 12, SHARE["dim"]))
    np.testing.assert_allclose(ref.attention(p, u, SHARE, 0), ref.attention(p, u, SHARE, 37), rtol=1e-4, atol=1e-5)


def test_the_absorbed_form_gives_the_scores_and_the_output_of_the_expanded_form():
    """What a decoder with a latent cache computes: ``q_n W_kvb,kᵀ`` against the normed latent itself, and the
    weighted latents through ``W_kvb,v``. Pinned against the expanded form that the trainer runs."""
    from distribuuuu_tpu.ops import attention

    s = SHARE
    h, dn, dv = s["attn_heads"], s["qk_nope_dim"], s["v_head_dim"]
    p = {k.split(".")[1]: v for k, v in seeded(dict(s, pattern="D"))[0].items() if k.startswith("L0.")}
    u = jax.random.normal(jax.random.key(1), (2, 10, s["dim"]))
    q_n, q_r, latent, k_r, k_n, v = ref.latent_parts(p, u, s)
    w_k, w_v = jnp.split(p["kv_b"].reshape(s["kv_latent"], h, dn + dv), (dn,), axis=-1)  # [latent, H, ·]
    expanded = jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n, precision=ref.HI) + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r, precision=ref.HI)
    absorbed_q = jnp.einsum("bqhd,chd->bqhc", q_n, w_k, precision=ref.HI)  # the query in the latent's space
    absorbed = jnp.einsum("bqhc,bkc->bhqk", absorbed_q, latent, precision=ref.HI) + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r, precision=ref.HI)
    np.testing.assert_allclose(absorbed, expanded, rtol=2e-4, atol=2e-6)
    weights = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((10, 10), bool)), absorbed * (dn + s["qk_rope_dim"]) ** -0.5, -jnp.inf), axis=-1)
    out = jnp.einsum("bqhc,chd->bqhd", jnp.einsum("bhqk,bkc->bqhc", weights, latent, precision=ref.HI), w_v, precision=ref.HI)
    core = attention.latent_causal_attention(jnp.concatenate([q_n, q_r], axis=-1), k_n, k_r, v)
    np.testing.assert_allclose(out.reshape(2, 10, h * dv), core, rtol=2e-4, atol=2e-6)


# -- (b) the stack takes layers before its repeats ------------------------------------------------------

@pytest.mark.parametrize("pattern,unit,prefixes", [
    ("DEEEE", (1, 1, 4), [("L0", "D", 0), ("U0", "E", 4)]),
    ("EMEMEMEMEM*", (0, 2, 5), [("U0", "E", 5), ("U1", "M", 5), ("L10", "*", 0)]),
    ("GGGA", (0, 1, 3), [("U0", "G", 3), ("L3", "A", 0)]),
    ("D", (0, 1, 1), [("L0", "D", 0)]),
    ("EEEE", (0, 1, 4), [("U0", "E", 4)]),
    ("DEEX", (1, 1, 2), [("L0", "D", 0), ("U0", "E", 2), ("L3", "X", 0)]),
])
def test_repeated_unit_and_layer_prefixes_take_layers_before_the_repeats(pattern, unit, prefixes):
    assert token_lm.repeated_unit(pattern) == unit
    assert token_lm.layer_prefixes(pattern) == prefixes


def test_a_leading_dense_layer_stands_alone_and_the_expert_layers_are_one_scan(fresh_cfg):
    fresh_cfg.LM.LOSS_BLOCK = 16
    model = model_of("DEEEE", SHARE)
    shapes = dv3().param_shapes(model.sizes)
    assert list(shapes)[:2] == ["embed", "L0_norm"] and shapes["L0_ff1"] == (32, 80) and "L0_router" not in shapes
    assert shapes["U0_w1"] == (4, 4, 32, 48) and shapes["U0_kv_b"] == (4, 16, 4 * 14) and "U0_ff1" not in shapes
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), model.dummy_input(0)))
    assert set(variables["batch_stats"]) == {"U0_b_corr"} and variables["batch_stats"]["U0_b_corr"].shape == (4, 16)
    tokens = tokens_of(0, SHARE["vocab"])

    def loops(pattern):
        model = model_of(pattern, SHARE)
        variables = jax.eval_shape(lambda: model.init(jax.random.key(0), model.dummy_input(0)))
        return jax.jit(lambda v: model.apply(v, tokens[:, :-1])).lower(variables).as_text().count("stablehlo.while")

    # the expert layers are one loop however many they are, and the dense layer before them adds none: one loop more
    # than the pattern in which nothing repeats (whose expert layer has a search's loop of its own)
    assert loops("DEEEE") == loops("DEE") == loops("DE") + 1


# -- (c) the sigmoid router over gated experts, 16 held of 128 and 6 a token ----------------------------

def test_sigmoid_router_matches_the_references_dense_weights():
    from distribuuuu_tpu.parallel import moe

    logits = jax.random.normal(jax.random.key(0), (20, 16))
    bias = 0.3 * jax.random.normal(jax.random.key(1), (16,))
    idx, w = moe.sigmoid_topk_route(logits, 3, bias, 2.448)
    dense = jnp.zeros_like(logits).at[jnp.arange(20)[:, None], idx].set(w)
    want = ref.route({"router": jnp.eye(16)}, bias, logits, dict(top_k=3, routed_scale=2.448))
    np.testing.assert_allclose(dense, want, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.448, rtol=1e-6)  # normalised over the choice, then scaled
    # the buffer moves the choice and not the weights: a lifted expert is chosen by all, at its own score
    lifted, w_lifted = moe.sigmoid_topk_route(logits, 3, bias.at[5].add(10.0), 2.448)
    assert bool(jnp.all(jnp.any(lifted == 5, axis=-1)))
    scores = jax.nn.sigmoid(logits)
    at5 = jnp.sum(jnp.where(lifted == 5, w_lifted, 0.0), axis=-1)
    np.testing.assert_allclose(at5, 2.448 * scores[:, 5] / jnp.sum(jnp.take_along_axis(scores, lifted, 1), axis=-1), rtol=1e-6)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_held_gated_experts_at_16_of_128_and_6_a_token_match_one_expert_at_a_time(kernels, monkeypatch):
    """The cell's counts (16 held of 128, the second eighth; 6 a token by sigmoid score, scaled) at small widths:
    `held_experts` with ``silu(gate) ⊙ up`` between its products through XLA's batched products and through
    `ops/grouped.py`'s kernel pair in the interpreter, against the reference's loop over the held experts."""
    from distribuuuu_tpu.parallel import moe

    monkeypatch.setattr(moe, "BLOCK", 8)
    monkeypatch.setattr(moe, "_takes_the_kernels", lambda *_: kernels)
    tokens, experts, held, first, k = 96, 128, 16, 16, 6
    dim, width = (128, 128) if kernels else (16, 24)
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (tokens, dim))
    w1, w2 = 0.3 * jax.random.normal(ks[1], (held, dim, 2 * width)), 0.3 * jax.random.normal(ks[2], (held, width, dim))
    router = jax.random.normal(ks[3], (dim, experts)) * dim ** -0.5
    sizes = dict(top_k=k, routed_scale=2.448, experts_held=held, expert_first=first)
    rows = moe.round_rows_for(tokens, k, experts, held)

    def program(x, w1, w2):
        idx, w = moe.sigmoid_topk_route(jnp.dot(x, router, precision=ref.HI), k, jnp.zeros((experts,)), 2.448)
        return moe.held_experts(x, idx, w, w1, w2, first, rows, between=moe.silu_gated)

    def one_at_a_time(x, w1, w2):
        weights = ref.route({"router": router}, jnp.zeros((experts,)), x, sizes)
        return sum(weights[:, first + e, None] * ref.mm(ref.silu_gated(ref.mm(x, w1[e])), w2[e]) for e in range(held))

    y, counts = jax.jit(program)(x, w1, w2)
    chosen = ref.route({"router": router}, jnp.zeros((experts,)), x, sizes) > 0
    assert counts.tolist() == jnp.sum(chosen[:, first:first + held], axis=0).tolist() and int(jnp.sum(counts)) > 0
    np.testing.assert_allclose(y, one_at_a_time(x, w1, w2), rtol=2e-4, atol=2e-5)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2)))(x, w1, w2)
    for got, want in zip(grads(lambda *a: program(*a)[0]), grads(one_at_a_time)):
        assert rel(got, want, floor=1e-6) <= 2e-4


# -- (d) program against reference: loss, every gradient leaf, the checkpoint, three optimizer steps -----

def _program_loss_and_grads(model, tree, buffers, tokens):
    def loss(p):
        return trainer._forward_loss_lm(model, p, buffers, {"tokens": tokens})[0]

    return jax.jit(jax.value_and_grad(loss))(tree)


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [(jnp.float32, 2e-6, 2e-4), (jnp.bfloat16, 2e-3, 6e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_loss_and_every_gradient_leaf_match_the_reference(fresh_cfg, pattern, dtype, loss_tol, grad_tol):
    """float32 tight (the two differ in the order of sums alone); bfloat16 at the tolerance its 8 bits of
    mantissa in every product's operands give. A leaf's gap is taken over its own norm or a hundredth of the
    median leaf's, whichever is larger."""
    fresh_cfg.LM.LOSS_BLOCK = 16
    sizes = dict(SHARE, pattern=pattern)
    model = model_of(pattern, SHARE, dtype)
    params, stats = seeded(sizes)
    tokens = tokens_of(5, SHARE["vocab"])
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: ref.loss_fn(p, stats, tokens, sizes)))(params)
    got_loss, got = _program_loss_and_grads(model, to_program(params, pattern), to_program(stats, pattern), tokens)
    assert abs(float(got_loss) - float(want_loss)) <= loss_tol * abs(float(want_loss))
    want_tree = to_program(want, pattern)
    assert set(got) == set(want_tree) == set(dv3().param_shapes(model.sizes))
    floor = 1e-2 * statistics.median(float(jnp.linalg.norm(v)) for v in want_tree.values())
    for name in want_tree:
        # the router reads a bfloat16 stream: a near-tie at the third score goes the other way for a token or two
        # of 48, and with it that token's whole part of the gradient of the router and of the two experts it swapped
        tol = 0.15 if dtype == jnp.bfloat16 and name.endswith(("_router", "_w1", "_w2")) else grad_tol
        assert rel(got[name], want_tree[name], floor) <= tol, name


@pytest.mark.parametrize("pattern", ["DEEEE", "DE", "EE"], ids=["dense_then_scanned", "unscanned", "scanned"])
def test_remat_under_the_policy_computes_what_no_remat_computes(fresh_cfg, pattern):
    """A checkpoint chooses what is stored and what is computed again, and adds no cast: float32, the same
    arithmetic, so the loss and every gradient leaf agree to rounding of the sums' order."""
    fresh_cfg.LM.LOSS_BLOCK = 16
    params, stats = seeded(dict(SHARE, pattern=pattern))
    tokens = tokens_of(5, SHARE["vocab"])
    plain, remat = model_of(pattern, SHARE, remat=False), model_of(pattern, SHARE, remat=True)
    tree, buffers = to_program(params, pattern), to_program(stats, pattern)
    want_loss, want = _program_loss_and_grads(plain, tree, buffers, tokens)
    got_loss, got = _program_loss_and_grads(remat, tree, buffers, tokens)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert set(got) == set(want)
    floor = 1e-2 * statistics.median(float(jnp.linalg.norm(v)) for v in want.values())
    for name in want:
        assert rel(got[name], want[name], floor) <= 1e-6, name


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (checkpoint, scan, cond, jit)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_gradient_under_the_policy_routes_once_and_runs_the_core_once_beside_its_own_blocks(fresh_cfg):
    """A name kept is named once, on the way forward: the backward pass reads the stored value, where a value not
    kept would be named again in the layer's recomputation. So the router's product and `top_k` stand once in the
    gradient, and the core's masked softmax twice (forward, and each block's own rematerialisation) where a layer
    that did not keep the core's output would hold it three times. XLA's blocks name no log-sum-exp."""
    from distribuuuu_tpu.ops.attention import CAUSAL_LSE

    m = dv3()
    fresh_cfg.LM.LOSS_BLOCK = 16
    tokens = tokens_of(5, SHARE["vocab"])
    model = model_of("E", SHARE, remat=True)
    params, stats = seeded(dict(SHARE, pattern="E"))
    loss = lambda p: trainer._forward_loss_lm(model, p, to_program(stats, "E"), {"tokens": tokens})[0]
    eqns = list(_equations(jax.make_jaxpr(jax.grad(loss))(to_program(params, "E")).jaxpr))
    assert sorted(e.params["name"] for e in eqns if e.primitive.name == "name") == sorted(set(m.KEPT) - {CAUSAL_LSE})
    assert sum(e.primitive.name == "top_k" for e in eqns) == 1
    assert sum(e.primitive.name == "dot_general" and "HIGHEST" in str(e.params["precision"]) for e in eqns) == 3
    # the core's softmax is the only reduction over keys to a maximum: one a block (one block at this length), twice
    assert sum(e.primitive.name == "reduce_max" and e.invars[0].aval.ndim == 5 for e in eqns) == 2


@pytest.mark.parametrize("keeps_lse", [True, False], ids=["kept", "lse_recomputed"])
def test_on_the_kernels_route_the_forward_kernel_runs_once_a_layer_under_the_policy(fresh_cfg, monkeypatch, keeps_lse):
    """The cell's pattern (a leading dense layer, then the expert layers as one scanned unit) at toy widths and 128
    tokens, the route forced to the kernel pair inside a mesh: `KEPT` holds the core's output and log-sum-exp,
    which are the residuals the backward kernel reads, so the gradient's program holds the forward kernel once a
    layer (the leading one and the unit's body) and never in a recomputation; without the log-sum-exp kept the
    layers' recomputations run it again."""
    from distribuuuu_tpu.ops import attention, causal_attention

    m = dv3()
    monkeypatch.setattr(causal_attention, "fits", lambda *a: True)
    monkeypatch.setattr(m.DeepseekV3, "kept", m.KEPT if keeps_lse else tuple(set(m.KEPT) - {attention.CAUSAL_LSE}))
    fresh_cfg.LM.LOSS_BLOCK = 64
    model = model_of("DEEEE", SHARE, remat=True)
    params, stats = seeded(dict(SHARE, pattern="DEEEE"))
    tokens = tokens_of(5, SHARE["vocab"], rows=1, length=128)
    loss = lambda p: trainer._forward_loss_lm(model, p, to_program(stats, "DEEEE"), {"tokens": tokens})[0]
    with jax.set_mesh(data_mesh(1)):
        eqns = list(_equations(jax.make_jaxpr(jax.grad(loss))(to_program(params, "DEEEE")).jaxpr))
    kernels = [e.params["name"] for e in eqns if e.primitive.name == "pallas_call"]
    assert kernels.count("dtpu_causal_attn_bwd") == 2
    assert kernels.count("dtpu_causal_attn_fwd") == (2 if keeps_lse else 4)


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
    correction buffers ride the state untouched and the routing counters ride the metrics."""
    cfg = fresh_cfg
    monkeypatch.setattr(optim, "FACTOR_MIN_DIM", 16)  # so that the toy's matrices are factored, as the cell's are
    cfg.TRAIN.TASK, cfg.OPTIM.OPTIMIZER, cfg.LM.LOSS_BLOCK, cfg.OPTIM.WEIGHT_DECAY = "lm", "adafactor", 16, 0.0
    sizes = dict(SHARE, pattern=pattern)
    model = model_of(pattern, SHARE)
    mesh = data_mesh(1)
    state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
    params, stats = seeded(sizes, seed=4)
    buffers = to_program(stats, pattern)
    assert set(state.batch_stats) == set(buffers)  # an expert layer's router has its buffer, a dense layer none
    state = state.replace(params=jax.tree.map(jnp.copy, to_program(params, pattern)),  # the step donates its state
                          batch_stats=jax.tree.map(jnp.copy, buffers))
    step = trainer.make_train_step(model, tx, mesh, topk=5)
    ref_params = to_program(params, pattern)  # the reference follows in the program's leaves
    zeros = lambda p: ((jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-1])),
                        jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-2])))
                       if p.ndim >= 2 and sorted(p.shape)[-2] >= 16 else (jnp.zeros_like(p),))
    ref_state = {"t": 0, "v": {k: zeros(p) for k, p in ref_params.items()}}
    flat = params
    ref_grads = jax.jit(jax.value_and_grad(lambda p, tokens: ref.loss_fn(p, stats, tokens, sizes)))
    for i in range(3):
        tokens = tokens_of(10 + i, SHARE["vocab"])
        state, metrics = step(state, {"tokens": tokens}, jnp.float32(0.01), jax.random.key(1))
        loss, grads = ref_grads(flat, tokens)
        ref_params, ref_state = _adafactor(ref_params, to_program(grads, pattern), ref_state, 0.01, 16)
        flat = _token_layers.from_program(ref_params, pattern)
        assert float(metrics["loss_sum"] / metrics["n"]) == pytest.approx(float(loss), rel=2e-5)
        if "E" in pattern:
            assert set(obs.WINDOW_COUNTERS) <= set(metrics)
    for name, value in buffers.items():
        np.testing.assert_array_equal(state.batch_stats[name], value)  # a buffer of the checkpoint: no gradient trains it
    # Adafactor divides a gradient by its own size, entry by entry or row and column: where the true gradient is
    # zero and what is computed is rounding (a router's column of an expert that no token chose beside a held one,
    # at 50 tokens) the update is the rounding's sign at full size, in the program and in the reference alike, so
    # such a leaf may differ by what three steps can move it (3 %); and a router that differs so routes steps two
    # and three with other weights, which the experts' leaves then show (some parts in a thousand)
    for name, value in ref_params.items():
        assert rel(state.params[name], value) <= (3e-2 if name.split("_", 1)[-1] == "router" else 5e-3), name


# -- (e) the share and the model: what the shares give adds up to the uncut expert block ----------------

def test_the_shares_of_an_expert_block_add_up_to_the_uncut_reference():
    """16 experts over 4 shares: the routed parts all the shares give, with the router, its correction buffer
    and the shared experts, which every chip computes alike, counted once, add up to the uncut reference's block."""
    m = dv3()
    ways = FULL["experts"] // SHARE["experts_held"]
    params, stats = seeded(dict(FULL, pattern="E"))
    params = {k.split(".")[1]: v for k, v in params.items() if k.startswith("L0.")}
    b_corr = stats["L0.b_corr"]
    h = jax.random.normal(jax.random.key(2), (ROWS, LENGTH, FULL["dim"]))
    x = ref.rms_norm(h, params["post_norm"], FULL["eps"])
    want = ref.experts(params, b_corr, x, FULL)
    assert not np.allclose(want, ref.experts(params, jnp.zeros_like(b_corr), x, FULL), atol=1e-4)  # the buffer counts
    shared = ref.shared_experts(params, x.reshape(-1, FULL["dim"])).reshape(h.shape)
    total, loads = 0.0, []
    for rank in range(ways):
        held = slice(rank * SHARE["experts_held"], (rank + 1) * SHARE["experts_held"])
        sizes = m.Sizes(pattern="E", **dict(SHARE, expert_first=held.start))
        p = dict(params, w1=params["w1"][held], w2=params["w2"][held])
        out, counts = jax.jit(lambda p, x, sizes=sizes: m.expert_block(p, b_corr, x, sizes, jnp.float32))(p, x)
        total = total + out
        loads.append(counts)
    total = total - (ways - 1) * shared  # every rank added the shared experts whole: count them once
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-6)
    assert int(sum(jnp.sum(c) for c in loads)) == ROWS * LENGTH * FULL["top_k"]  # every slot landed on one share


# -- (f) the scopes, the factory, the shipped configuration ---------------------------------------------

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
        model = model_of("DEE", SHARE)
        mesh = data_mesh(1)
        state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
        step = trainer.make_train_step(model, tx, mesh, topk=5)
        batch = {"tokens": tokens_of(0, SHARE["vocab"])}
        text = step.lower(state, batch, jnp.float32(0.1), jax.random.key(1)).compile().as_text()
        _COMPILED_NAMES.extend(re.findall(r'op_name="([^"]*)"', text))
    return _COMPILED_NAMES


@pytest.mark.parametrize("scope", ["latent_attn", "causal_attn", "mixer_proj", "dense_ffn", "moe_route", "moe_experts",
                                   "lm_head"])
def test_compiled_step_names_the_model_scopes_in_both_passes(fresh_cfg, no_compile_cache, scope):
    from distribuuuu_tpu.obs import trace as obs_trace

    assert scope in obs_trace.MODEL_SCOPES
    under = [n for n in _compiled_names(fresh_cfg) if f"/dtpu.{scope}/" in n]
    assert any("transpose(" in n for n in under), f"no backward op under dtpu.{scope}"
    assert any("transpose(" not in n for n in under), f"no forward op under dtpu.{scope}"


def test_the_causal_core_lies_inside_the_latent_scope_and_holds_no_projection(fresh_cfg, no_compile_cache):
    names = _compiled_names(fresh_cfg)
    # the core alone: no projection's product stands under its scope (the layers' products are 32 wide on one side)
    core = [n for n in names if "/dtpu.latent_attn/" in n and "dot_general" in n]
    assert core and all("/L0/" in n or "/U0/" in n or "while" in n for n in core)
    assert not any("/dtpu.mixer_proj/" in n for n in core)
    # every op of the causal core stands inside latent attention's scope, which adds only what reshapes its heads
    assert all("/dtpu.latent_attn/" in n for n in names if "/dtpu.causal_attn/" in n)


def test_the_family_takes_the_keys_of_the_section_that_it_names(fresh_cfg):
    """One ``LM`` section for the three families: the factory builds from the keys its ``Sizes`` names, whatever
    else the section holds; the trainer tests no model's name."""
    cfg = fresh_cfg
    cfg.MODEL.ARCH, cfg.MODEL.MODULE, cfg.TRAIN.TASK = "deepseek_v3", "distribuuuu_tpu.models.deepseek_v3", "lm"
    cfg.LM.PATTERN, cfg.LM.VOCAB, cfg.LM.DIM, cfg.LM.ATTN_HEADS = "DEEEE", 64, 32, 4
    cfg.LM.KV_LATENT, cfg.LM.QK_NOPE_DIM, cfg.LM.QK_ROPE_DIM, cfg.LM.V_HEAD_DIM, cfg.LM.DENSE_WIDTH = 16, 8, 4, 6, 40
    cfg.LM.EXPERTS, cfg.LM.EXPERTS_HELD, cfg.LM.TOP_K, cfg.LM.EXPERT_WIDTH, cfg.LM.SHARED_WIDTH = 16, 4, 3, 24, 48
    cfg.LM.ROUTED_SCALE, cfg.LM.NORM_EPS, cfg.LM.ROPE_THETA = 2.448, 1e-6, 1e6
    model = trainer._build_cfg_model()
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), model.dummy_input(0)))["params"]
    assert shapes["L0_ff1"].shape == (32, 80) and shapes["U0_q"].shape == (4, 32, 48) and shapes["embed"].shape == (64, 32)
    assert model.sizes.eps == 1e-6 and model.sizes.routed_scale == 2.448 and model.sizes.rope_theta == 1e6
    with pytest.raises(ValueError, match="one of D, E"):
        dv3().layer_shapes("G", model.sizes)
    cfg.TRAIN.TASK = "classify"
    with pytest.raises(ValueError, match="TRAIN.TASK 'lm'"):
        trainer._build_cfg_model()


def test_shipped_yaml_builds_the_configurations_576m_parameters(fresh_cfg):
    """Shapes only: the published widths with the held shares count 576.0 M parameters, as the issue reckoned."""
    from distribuuuu_tpu import config

    config.cfg.merge_from_file(os.path.join(os.path.dirname(HERE), "config", "kanana2_30b.yaml"))
    model = trainer._build_cfg_model()
    shapes = dv3().param_shapes(model.sizes)
    count = lambda *leaves: sum(int(np.prod(shapes[leaf])) for leaf in leaves)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 575_955_456
    assert count("L0_q", "L0_kv_a", "L0_kv_norm", "L0_kv_b", "L0_o") == 26_345_984  # a mixer
    assert count("L0_ff1", "L0_ff2") == 37_748_736                                   # layer 0's feed-forward
    assert count("U0_router", "U0_w1", "U0_w2", "U0_shared1", "U0_shared2") == 4 * 85_196_800
    assert shapes["U0_w1"] == (4, 16, 2048, 1536) and shapes["U0_kv_b"] == (4, 512, 8192) and shapes["embed"] == (16032, 2048)
    assert model.remat and model.dtype == jnp.bfloat16 and model.sizes.pattern == "DEEEE"
    assert 8 * model.sizes.experts_held == model.sizes.experts == 128
