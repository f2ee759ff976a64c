"""The token-sequence task and its model (TRAIN.TASK "lm", models/nemotron_h.py) against the plain
reference ``tests/reference/nemotron_h.py``: loss, every gradient leaf and three optimizer steps;
the chunked scan against the recurrence; causal grouped-query attention against a dense mask; the
shares of a layer add up to the uncut layer; no token-expert slot is dropped; the new scopes and
counters reach the compiled step and the journal. CPU, toy widths, seeded weights."""

from __future__ import annotations

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import obs, optim, resilience, trainer
from distribuuuu_tpu.models import token_lm
from distribuuuu_tpu.obs import trace as obs_trace
from distribuuuu_tpu.obs.journal import read_journal, validate_journal
from distribuuuu_tpu.runtime import data_mesh

import _token_layers

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(HERE, "reference", "nemotron_h.py"), "reference_nemotron_h")

# toy widths; the counts are the *uncut* layer's, of which the share tests take parts
FULL = dict(vocab=48, dim=32, layers_total=8, mamba_heads=8, mamba_head_dim=8, mamba_groups=4, ssm_state=16,
            conv_kernel=4, chunk=16, attn_heads=8, kv_heads=2, head_dim=8, experts=16, experts_held=16,
            expert_first=0, top_k=3, latent=16, expert_width=24, shared_width=40, routed_scale=5.0, eps=1e-5)
# one chip's share of it, as the configuration cuts the real model: an eighth... here a quarter
SHARE = dict(FULL, mamba_heads=2, mamba_groups=1, attn_heads=2, kv_heads=1, experts_held=4, expert_first=4)
ROWS, LENGTH = 2, 24  # no multiple of the chunk


def nh():
    """The program's module, imported when a test asks: it registers an arch, and ``list_models()`` is
    a parametrisation of other files."""
    from distribuuuu_tpu.models import nemotron_h

    return nemotron_h


def model_of(pattern: str, sizes: dict, dtype=jnp.float32, remat: bool = True):
    m = nh()
    return m.NemotronH(m.Sizes(pattern=pattern, **sizes), dtype=dtype, remat=remat)


def to_program(params: dict, stats: dict, model) -> tuple[dict, dict]:
    """The reference's per-layer leaves (``L3.w1``) in the program's flat tree (``U1_w1 [repeats, ...]``)."""
    m = nh()
    s = model.sizes
    layers_of = _token_layers.layers_of(s.pattern)

    def leaf(name, source):
        prefix, _, short = name.partition("_")
        if prefix not in layers_of:
            return source[name]
        where = layers_of[prefix]
        if isinstance(where, list):
            return jnp.stack([source[f"L{i}.{short}"] for i in where])
        return source[f"L{where}.{short}"]

    tree = {name: leaf(name, params) for name in m.param_shapes(s)}
    buffers = {f"{prefix}_b_corr": leaf(f"{prefix}_b_corr", stats)
               for prefix, kind, _ in m.layer_prefixes(s) if kind == "E"}
    return tree, buffers


def tokens_of(seed: int, vocab: int, rows: int = ROWS, length: int = LENGTH):
    return jax.random.randint(jax.random.key(seed), (rows, length + 1), 0, vocab)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# -- (a) program against reference: loss, every gradient leaf, three optimizer steps ------------

PATTERNS = ["M", "*", "E", "EMEMEMEMEM*"]


def _program_loss_and_grads(model, tree, buffers, tokens):
    def loss(p):
        return trainer._forward_loss_lm(model, p, buffers, {"tokens": tokens})[0]

    return jax.jit(jax.value_and_grad(loss))(tree)


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [(jnp.float32, 2e-6, 2e-4), (jnp.bfloat16, 2e-3, 6e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_loss_and_every_gradient_leaf_match_the_reference(fresh_cfg, pattern, dtype, loss_tol, grad_tol):
    """float32 tight (the two differ in the order of sums alone); bfloat16 at the tolerance its 8 bits of
    mantissa in every product's operands give: 2e-3 on the loss, 6 % on a leaf's gradient."""
    fresh_cfg.LM.LOSS_BLOCK = 16
    sizes = dict(SHARE, pattern=pattern)
    model = model_of(pattern, SHARE, dtype)
    params, stats = ref.init(jax.random.key(3), sizes), ref.init_stats(sizes)
    tokens = tokens_of(5, SHARE["vocab"])
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: ref.loss_fn(p, stats, tokens, sizes)))(params)
    got_loss, got = _program_loss_and_grads(model, *to_program(params, stats, model), tokens)
    assert abs(float(got_loss) - float(want_loss)) <= loss_tol * abs(float(want_loss))
    want_tree, _ = to_program(want, stats, model)
    assert set(got) == set(want_tree)
    for name in want_tree:
        assert rel(got[name], want_tree[name]) <= grad_tol, name


# -- (a') the layer checkpoint's policy: the same numbers, and the routing and the kept products once ----

@pytest.mark.parametrize("pattern", ["EMEMEMEMEM*", "EM*", "EMEM"], ids=["scanned_then_attention", "unscanned", "scanned"])
def test_remat_under_the_policy_computes_what_no_remat_computes(fresh_cfg, pattern):
    """A checkpoint chooses what is stored and what is computed again, and adds no cast: float32, the same
    arithmetic, so the loss and every gradient leaf agree to rounding of the sums' order."""
    fresh_cfg.LM.LOSS_BLOCK = 16
    sizes = dict(SHARE, pattern=pattern)
    params, stats = ref.init(jax.random.key(3), sizes), ref.init_stats(sizes)
    tokens = tokens_of(5, SHARE["vocab"])
    plain, remat = model_of(pattern, SHARE, remat=False), model_of(pattern, SHARE, remat=True)
    want_loss, want = _program_loss_and_grads(plain, *to_program(params, stats, plain), tokens)
    got_loss, got = _program_loss_and_grads(remat, *to_program(params, stats, remat), tokens)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert set(got) == set(want)
    for name in want:
        assert rel(got[name], want[name]) <= 1e-6, name


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (checkpoint, scan, cond, jit)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_gradient_under_the_policy_routes_once_and_reads_the_kept_values(fresh_cfg, capsys):
    """The counts themselves, of one trace in this process (`jax.checkpoint` caches the trace of `layer`
    by the function's identity, so a second variant traced beside it would read the first's jaxpr). With no
    policy the gradient of one ``E`` layer holds `top_k` twice and four products at `HIGHEST` (the router's:
    forward, recomputed, and the two of the backward pass). A name kept is named once, on the way forward:
    the backward pass reads the stored value, where a value not kept would be named again in the recomputation."""
    m = nh()
    fresh_cfg.LM.LOSS_BLOCK = 16
    tokens = tokens_of(5, SHARE["vocab"])

    def gradient_of(pattern):
        model = model_of(pattern, SHARE, remat=True)
        tree, buffers = to_program(ref.init(jax.random.key(3), dict(SHARE, pattern=pattern)),
                                   ref.init_stats(dict(SHARE, pattern=pattern)), model)
        loss = lambda p: trainer._forward_loss_lm(model, p, buffers, {"tokens": tokens})[0]
        return loss, tree, list(_equations(jax.make_jaxpr(jax.grad(loss))(tree).jaxpr))

    _, _, eqns = gradient_of("E")
    assert sum(e.primitive.name == "top_k" for e in eqns) == 1
    assert sum(e.primitive.name == "dot_general" and "HIGHEST" in str(e.params["precision"]) for e in eqns) == 3
    loss, tree, eqns = gradient_of("EM")  # no unit repeats: two layers, each under its own checkpoint
    assert sorted(e.params["name"] for e in eqns if e.primitive.name == "name") == sorted(m.KEPT)  # each once
    jax.ad_checkpoint.print_saved_residuals(loss, tree)
    listed = capsys.readouterr().out.splitlines()
    tokens_here = ROWS * LENGTH
    # the chosen ids under their name; a float kept passes a `reduce_precision` that keeps the forward's own
    # value from being merged with it, so it is listed by that op, at its line of the mixer
    assert any(line.startswith(f"i32[{tokens_here},{SHARE['top_k']}] named '{m.ROUTE_IDX}'") for line in listed)
    kept_floats = [line.split()[0] for line in listed if "reduce_precision" in line and "_mixer)" in line]
    in_proj = m.layer_shapes("M", model_of("M", SHARE).sizes)["in_proj"][1]
    assert sorted(kept_floats) == sorted(
        [f"f32[{tokens_here},{width}]" for width in (SHARE["experts"], SHARE["latent"], SHARE["shared_width"])]
        + [f"f32[{ROWS},{LENGTH},{in_proj}]"])


def _lamb(params, grads, state, lr, hp):
    b1, b2, eps, wd = hp
    t = state["t"] + 1
    out, mu, nu = {}, {}, {}
    for k, p in params.items():
        mu[k] = b1 * state["mu"][k] + (1 - b1) * grads[k]
        nu[k] = b2 * state["nu"][k] + (1 - b2) * grads[k] ** 2
        u = (mu[k] / (1 - b1 ** t)) / (jnp.sqrt(nu[k] / (1 - b2 ** t)) + eps)
        u = u + wd * p if p.ndim > 1 else u
        pn, un = jnp.linalg.norm(p), jnp.linalg.norm(u)
        out[k] = p - lr * jnp.where((pn == 0) | (un == 0), 1.0, pn / un) * u
    return out, {"t": t, "mu": mu, "nu": nu}


@pytest.mark.parametrize("pattern", PATTERNS)
def test_three_lamb_steps_through_the_trainer_match_the_reference(fresh_cfg, pattern):
    """The jitted train step as the trainer builds it (task lm, guard, donated state), float32, against
    the reference's gradients under LAMB's plain formulas; the routing counters ride the metrics."""
    cfg = fresh_cfg
    cfg.TRAIN.TASK, cfg.OPTIM.OPTIMIZER, cfg.LM.LOSS_BLOCK = "lm", "lamb", 16
    cfg.OPTIM.WEIGHT_DECAY = 0.01
    sizes = dict(SHARE, pattern=pattern)
    model = model_of(pattern, SHARE)
    mesh = data_mesh(1)
    state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
    params, stats = ref.init(jax.random.key(4), sizes), ref.init_stats(sizes)
    tree, buffers = jax.tree.map(jnp.copy, to_program(params, stats, model))  # the step donates its state
    state = state.replace(params=tree, batch_stats=buffers)
    step = trainer.make_train_step(model, tx, mesh, topk=5)
    ref_params = to_program(params, stats, model)[0]  # the reference follows in the program's leaves
    ref_state = {"t": 0, "mu": jax.tree.map(jnp.zeros_like, ref_params), "nu": jax.tree.map(jnp.zeros_like, ref_params)}
    hp = (cfg.OPTIM.BETA1, cfg.OPTIM.BETA2, cfg.OPTIM.EPS, cfg.OPTIM.WEIGHT_DECAY)
    flat = params
    ref_grads = jax.jit(jax.value_and_grad(lambda p, tokens: ref.loss_fn(p, stats, tokens, sizes)))
    for i in range(3):
        tokens = tokens_of(10 + i, SHARE["vocab"])
        state, metrics = step(state, {"tokens": tokens}, jnp.float32(0.01), jax.random.key(1))
        loss, grads = ref_grads(flat, tokens)
        ref_params, ref_state = _lamb(ref_params, to_program(grads, stats, model)[0], ref_state, 0.01, hp)
        flat = _token_layers.from_program(ref_params, model.sizes.pattern)
        assert float(metrics["loss_sum"] / metrics["n"]) == pytest.approx(float(loss), rel=2e-5)
        if "E" in pattern:
            assert set(obs.WINDOW_COUNTERS) <= set(metrics)
    for name, value in ref_params.items():
        assert rel(state.params[name], value) <= 2e-4, name


def _adafactor(params, grads, state, lr, min_dim):
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


def test_adafactor_follows_its_plain_formulas_for_three_steps(fresh_cfg, monkeypatch):
    """Factored leaves (two axes of at least FACTOR_MIN_DIM), a stack of them, and unfactored ones."""
    monkeypatch.setattr(optim, "FACTOR_MIN_DIM", 8)
    fresh_cfg.OPTIM.OPTIMIZER, fresh_cfg.OPTIM.WEIGHT_DECAY = "adafactor", 0.0
    tx = optim.construct_optimizer()
    key = jax.random.key(0)
    params = {"matrix": jax.random.normal(key, (16, 12)), "stack": jax.random.normal(key, (3, 10, 24)),
              "vector": jax.random.normal(key, (7,)), "thin": jax.random.normal(key, (4, 20))}
    zeros = lambda p: ((jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-1])),
                        jnp.zeros(np.delete(p.shape, np.argsort(p.shape)[-2])))
                       if p.ndim >= 2 and sorted(p.shape)[-2] >= 8 else (jnp.zeros_like(p),))
    got, want = dict(params), dict(params)
    state, want_state = tx.init(params), {"t": 0, "v": {k: zeros(p) for k, p in params.items()}}
    for i in range(3):
        grads = {k: (i + 1.0) * jax.random.normal(jax.random.fold_in(key, i), p.shape) for k, p in params.items()}
        updates, state = tx.update(grads, state, got)
        got = optim.apply_updates_with_lr(got, updates, 0.05)
        want, want_state = _adafactor(want, grads, want_state, 0.05, 8)
        for k in params:
            np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-6, err_msg=f"{k} step {i}")


def test_adafactor_refuses_fsdp_shards(fresh_cfg):
    fresh_cfg.OPTIM.OPTIMIZER = "adafactor"
    with pytest.raises(ValueError, match="FSDP"):
        optim.construct_optimizer(param_specs={}, fsdp_axis="fsdp")


# -- (b) the chunked scan against the recurrence --------------------------------------------------

def _scan_inputs(length: int, seed: int = 0):
    b, h, p, g, n = 2, 4, 8, 2, 16
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (b, length, h, p)), jax.nn.softplus(jax.random.normal(ks[1], (b, length, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,))), jax.random.normal(ks[3], (b, length, g, n)),
            jax.random.normal(ks[4], (b, length, g, n)), jax.random.normal(ks[5], (h,)))


def recurrence(x, dt, a, b, c, d_skip):
    """The scan's mathematics one `lax.scan` step a position, float32 throughout; shapes as `ops.ssm.ssd_scan`."""
    f32 = jnp.float32
    batch, _, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    rep = heads // groups

    def step(state, at_t):                                         # state [B, H, P, N]
        x_t, dt_t, b_t, c_t = at_t
        b_h, c_h = jnp.repeat(b_t, rep, axis=1), jnp.repeat(c_t, rep, axis=1)  # [B, H, N]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_h, precision=jax.lax.Precision.HIGHEST)

    time_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((batch, heads, p, n), f32), tuple(map(time_major, (x, dt, b, c))))
    return jnp.moveaxis(y, 0, 1) + d_skip.astype(f32)[:, None] * x


@pytest.mark.parametrize("length", [32, 40, 7], ids=["two_chunks", "not_a_multiple", "under_a_chunk"])
def test_chunked_scan_matches_the_recurrence_forward_and_gradient(length):
    from distribuuuu_tpu.ops import ssm

    args = _scan_inputs(length)
    weight = jax.random.normal(jax.random.key(9), args[0].shape)
    chunked = lambda *a: jnp.sum(ssm.ssd_scan(*a, 16) * weight)
    plain = lambda *a: jnp.sum(recurrence(*a) * weight)
    np.testing.assert_allclose(jax.jit(lambda *a: ssm.ssd_scan(*a, 16))(*args), jax.jit(recurrence)(*args),
                               rtol=2e-4, atol=2e-4)
    got = jax.jit(jax.grad(chunked, argnums=tuple(range(6))))(*args)
    want = jax.jit(jax.grad(plain, argnums=tuple(range(6))))(*args)
    for g, w, name in zip(got, want, ("x", "dt", "a", "b", "c", "d")):
        assert rel(g, w) <= 2e-4, name


def test_chunked_scan_carries_its_state_across_chunks():
    """What the planted fault ``no_carry`` leaves out: with the second chunk's input zeroed, its output is
    the first chunk's state read out, and not zero."""
    from distribuuuu_tpu.ops import ssm

    x, dt, a, b, c, d = _scan_inputs(32)
    x = x.at[:, 16:].set(0.0)
    y = jax.jit(lambda *a: ssm.ssd_scan(*a, 16))(x, dt, a, b, c, d)
    assert float(jnp.abs(y[:, 16:]).max()) > 1e-3
    np.testing.assert_allclose(y, jax.jit(recurrence)(x, dt, a, b, c, d), rtol=2e-4, atol=2e-4)


# -- (c) causal grouped-query attention ------------------------------------------------------------

@pytest.mark.parametrize("length,block", [(20, 8), (16, 16), (5, 8)], ids=["ragged_blocks", "one_block", "short"])
def test_causal_attention_matches_a_dense_mask(length, block):
    from distribuuuu_tpu.ops import attention

    heads, kv_heads, hd = 4, 2, 8
    qkv = jax.random.normal(jax.random.key(1), (2, length, (heads + 2 * kv_heads) * hd))

    def dense(qkv):
        q, k, v = jnp.split(qkv, (heads * hd, (heads + kv_heads) * hd), axis=-1)
        q = q.reshape(2, length, heads, hd)
        k, v = (jnp.repeat(t.reshape(2, length, kv_heads, hd), heads // kv_heads, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v).reshape(2, length, heads * hd)

    blocked = jax.jit(lambda qkv: attention.xla_causal_attention(qkv, heads, kv_heads, block))
    np.testing.assert_allclose(blocked(qkv), dense(qkv), rtol=2e-5, atol=2e-5)
    got = jax.jit(jax.grad(lambda t: jnp.sum(jnp.sin(blocked(t)))))(qkv)
    want = jax.jit(jax.grad(lambda t: jnp.sum(jnp.sin(dense(t)))))(qkv)
    assert rel(got, want) <= 2e-5
    # the entry point's second kind is this one
    np.testing.assert_allclose(attention.self_attention(qkv, heads, kv_heads=kv_heads, causal=True), dense(qkv),
                               rtol=2e-5, atol=2e-5)


def test_grouped_query_without_causal_is_refused():
    from distribuuuu_tpu.ops import attention

    with pytest.raises(ValueError, match="causal"):
        attention.self_attention(jnp.zeros((1, 4, 32)), 2, kv_heads=1)


# -- (d) the shares add up ---------------------------------------------------------------------------

def _slice_layer(kind: str, p: dict, rank: int, ways: int) -> dict:
    """Rank ``rank`` of ``ways``' share of one uncut layer's leaves, as the deployment divides it: Mamba
    heads with their B/C group and their slice of the gated norm, query heads with their key/value head,
    a block of experts; the norm, and for an expert layer router, latent projections and shared expert, whole."""
    f = FULL
    cols = lambda t, n: t[..., rank * n // ways:(rank + 1) * n // ways]
    if kind == "M":
        inner, bc, h = f["mamba_heads"] * f["mamba_head_dim"], f["mamba_groups"] * f["ssm_state"], f["mamba_heads"]
        parts = lambda t: jnp.split(t, (inner, 2 * inner, 2 * inner + bc, 2 * inner + 2 * bc), axis=-1)
        z, x, b, c, dt = parts(p["in_proj"])
        _, cx, cb, cc, _ = parts(jnp.pad(p["conv_w"], ((0, 0), (inner, h))))  # conv_w has no z and dt parts
        _, bx, bb, bcc, _ = parts(jnp.pad(p["conv_b"], (inner, h)))
        return {"norm": p["norm"],
                "in_proj": jnp.concatenate([cols(z, inner), cols(x, inner), cols(b, bc), cols(c, bc), cols(dt, h)], -1),
                "conv_w": jnp.concatenate([cols(cx, inner), cols(cb, bc), cols(cc, bc)], -1),
                "conv_b": jnp.concatenate([cols(bx, inner), cols(bb, bc), cols(bcc, bc)], -1),
                "dt_bias": cols(p["dt_bias"], h), "a_log": cols(p["a_log"], h), "d": cols(p["d"], h),
                "gnorm": cols(p["gnorm"], inner), "out_proj": p["out_proj"][rank * inner // ways:(rank + 1) * inner // ways]}
    if kind == "*":
        q, kv = f["attn_heads"] * f["head_dim"], f["kv_heads"] * f["head_dim"]
        kv_rank = rank * f["kv_heads"] // ways  # several ranks share a key/value head
        kv_cols = lambda t: t[:, kv_rank * f["head_dim"]:(kv_rank + 1) * f["head_dim"]]
        return {"norm": p["norm"], "q": cols(p["q"], q), "k": kv_cols(p["k"]), "v": kv_cols(p["v"]),
                "o": p["o"][rank * q // ways:(rank + 1) * q // ways]}
    e = f["experts"]
    return dict(p, w1=p["w1"][rank * e // ways:(rank + 1) * e // ways], w2=p["w2"][rank * e // ways:(rank + 1) * e // ways])


@pytest.mark.parametrize("kind", ["M", "*", "E"], ids=["mamba_heads", "attention_heads", "experts"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(kind):
    """The program's layer on each of 4 shares: their mixers' outputs sum to the uncut reference layer's,
    with what every chip computes alike (the residual, and an expert layer's shared expert and the
    part of the latent path that is linear in the mixture) counted once."""
    m = nh()
    ways = 4
    full = dict(FULL, pattern=kind)
    params = {k[3:]: v for k, v in ref.init(jax.random.key(7), full).items() if k.startswith("L0.")}
    b_corr = jnp.zeros((FULL["experts"],))
    h = jax.random.normal(jax.random.key(8), (ROWS, LENGTH, FULL["dim"]))
    want = jax.jit(lambda p, h: ref.layer(kind, p, b_corr, h, full))(params, h) - h
    share = m.Sizes(pattern=kind, **dict(SHARE))
    total = 0.0
    for rank in range(ways):
        sizes = share if kind != "E" else m.Sizes(pattern=kind, **dict(SHARE, expert_first=rank * SHARE["experts_held"]))
        out, counts = jax.jit(lambda p, h, sizes=sizes: m.layer(kind, p, b_corr, h, sizes))(
            _slice_layer(kind, params, rank, ways), h)
        total = total + (out - h)
    if kind == "E":  # every rank added the whole shared expert: count it once
        u = ref.rms_norm(h, params["norm"], FULL["eps"]).reshape(-1, FULL["dim"])
        shared = ref.mm(jnp.square(jax.nn.relu(ref.mm(u, params["shared1"]))), params["shared2"]).reshape(h.shape)
        total = total - (ways - 1) * shared
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# -- (e) no token-expert slot is dropped ---------------------------------------------------------------

@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("round_rows,block", [(8, 4), (16, 16), (256, 256)], ids=["five_rounds", "four_rounds", "roomy"])
def test_no_slot_is_dropped_when_every_token_chooses_the_same_experts(round_rows, block, kernels, monkeypatch):
    """Through both realisations of a block's products: XLA's batched ones, and `ops/grouped.py`'s
    kernel pair in the interpreter at widths it tiles (the route is steered here, not by an option)."""
    from distribuuuu_tpu.parallel import moe

    monkeypatch.setattr(moe, "BLOCK", block)
    monkeypatch.setattr(moe, "_takes_the_kernels", lambda *_: kernels)

    tokens, held, k = 64, 4, 3
    dim, width = (128, 256) if kernels else (16, 24)
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (tokens, dim))
    w1, w2 = 0.3 * jax.random.normal(ks[1], (held, dim, width)), 0.3 * jax.random.normal(ks[2], (held, width, dim))
    idx = jnp.tile(jnp.array([[5, 4, 9]]), (tokens, 1))  # experts 4 and 5 of the held 4..7, and an absent one
    w = jax.nn.softmax(jax.random.normal(ks[3], (tokens, k)), axis=-1)

    def dense(x, w, w1, w2):
        y = 0.0
        for e in range(held):
            gate = jnp.sum(jnp.where(idx == 4 + e, w, 0.0), axis=-1)
            y = y + (jnp.square(jax.nn.relu(x @ w1[e])) @ w2[e]) * gate[:, None]
        return y

    held_experts = functools.partial(moe.held_experts, between=moe.relu_squared)
    y, counts = jax.jit(lambda *a: held_experts(a[0], idx, *a[1:], 4, round_rows))(x, w, w1, w2)
    assert counts.tolist() == [tokens, tokens, 0, 0]  # every slot on a held expert is counted
    np.testing.assert_allclose(y, dense(x, w, w1, w2), rtol=2e-4, atol=2e-5)
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3)))(x, w, w1, w2)
    for got, want in zip(grads(lambda *a: held_experts(a[0], idx, *a[1:], 4, round_rows)[0]), grads(dense)):
        assert rel(got, want) <= 2e-4


def test_router_takes_top_k_of_score_plus_bias_and_scales_the_chosen_scores():
    from distribuuuu_tpu.parallel import moe

    logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 0.1]])
    bias = jnp.array([0.0, 0.0, 0.0, 10.0])  # lifts expert 3 into every choice, and out of no weight
    idx, w = moe.sigmoid_topk_route(logits, 2, bias, 5.0)
    assert sorted(idx[0].tolist()) == [0, 3] and sorted(idx[1].tolist()) == [2, 3]
    s = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 5.0, rtol=1e-6)
    np.testing.assert_allclose(w[0, idx[0].tolist().index(0)], 5.0 * s[0, 0] / (s[0, 0] + s[0, 3]), rtol=1e-6)
    assert moe.round_rows_for(8192, 22, 512, 8) == 6400 and moe.round_rows_for(8, 22, 512, 8) == 8 * 256


@pytest.mark.parametrize("k", [1, 3, 8])
def test_the_chosen_scores_are_the_gathered_ones_bit_for_bit(k):
    """The router picks the chosen scores by comparison (one fused pass on a TPU); what it returns and
    what it sends back to the logits is what `take_along_axis` would, to the last bit."""
    from distribuuuu_tpu.parallel import moe

    rng = np.random.default_rng(k)
    logits = jnp.asarray(3.0 * rng.standard_normal((40, 16)), jnp.float32)
    bias, weight = jnp.asarray(rng.standard_normal(16), jnp.float32), jnp.asarray(rng.standard_normal((40, k)), jnp.float32)

    def gathered(logits):
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + bias, k)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, 2.5 * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)

    routed = lambda logits: moe.sigmoid_topk_route(logits, k, bias, 2.5)
    for got, want in zip(routed(logits), gathered(logits)):
        np.testing.assert_array_equal(got, want)
    grad = lambda f: jax.grad(lambda x: jnp.sum(f(x)[1] * weight))(logits)
    np.testing.assert_array_equal(grad(routed), grad(gathered))


# -- (f) scopes in the compiled step, counters in the journal ------------------------------------------

@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _lm_run(cfg, pattern: str = "EM*", sizes: dict = SHARE, remat: bool = True):
    cfg.TRAIN.TASK, cfg.OPTIM.OPTIMIZER, cfg.LM.LOSS_BLOCK = "lm", "adafactor", 16
    model = model_of(pattern, sizes, remat=remat)
    mesh = data_mesh(1)
    state, tx = trainer.create_train_state(model, jax.random.key(0), mesh, 0)
    return mesh, state, trainer.make_train_step(model, tx, mesh, topk=5)


_COMPILED_NAMES: list = []  # the compiled step's op_names, once for the file's cases


def _compiled_names(cfg) -> list[str]:
    if not _COMPILED_NAMES:
        mesh, state, step = _lm_run(cfg)
        batch = {"tokens": tokens_of(0, SHARE["vocab"])}
        text = step.lower(state, batch, jnp.float32(0.1), jax.random.key(1)).compile().as_text()
        _COMPILED_NAMES.extend(re.findall(r'op_name="([^"]*)"', text))
    return _COMPILED_NAMES


# this family's of `MODEL_SCOPES` (the delta rule's: test_qwen3_next.py; latent attention's: test_deepseek_v3.py)
@pytest.mark.parametrize("scope", ["ssm_scan", "causal_attn", "mixer_proj", "dense_ffn", "moe_route", "moe_experts",
                                   "lm_head", "short_conv"])
def test_compiled_step_names_the_model_scopes_in_both_passes(fresh_cfg, no_compile_cache, scope):
    assert scope in obs_trace.MODEL_SCOPES
    under = [n for n in _compiled_names(fresh_cfg) if f"/dtpu.{scope}/" in n]
    assert any("transpose(" in n for n in under), f"no backward op under dtpu.{scope}"
    assert any("transpose(" not in n for n in under), f"no forward op under dtpu.{scope}"


def test_compiled_step_names_the_loss_and_the_optimizer(fresh_cfg, no_compile_cache):
    names = _compiled_names(fresh_cfg)
    assert any("jvp(dtpu.loss)" in n for n in names) and any("dtpu.optimizer" in n for n in names)
    assert all("jvp(dtpu.loss)" in n for n in names if "/dtpu.lm_head/" in n)  # the head's product inside the loss


def test_compiled_step_places_every_kernel_call_under_the_experts_scope(fresh_cfg, no_compile_cache, monkeypatch):
    """With the kernel pair taken (interpreted here), forward and backward: the benchmark reads
    `moe_experts_ms` by this scope, and a backward kernel outside it would be time it never sees."""
    from distribuuuu_tpu.parallel import moe

    monkeypatch.setattr(moe, "_takes_the_kernels", lambda *_: True)
    mesh, state, step = _lm_run(fresh_cfg, sizes=dict(SHARE, latent=128, expert_width=256))  # widths the kernels tile
    batch = {"tokens": tokens_of(0, SHARE["vocab"])}
    text = step.lower(state, batch, jnp.float32(0.1), jax.random.key(1)).as_text(debug_info=True)
    calls = [n for n in re.findall(r'"(jit\([^"]*)"', text) if "/pallas_call" in n]
    for kernel, passes in (("dtpu_moe_gmm", ("jvp(", "transpose(")), ("dtpu_moe_tgmm", ("transpose(",))):
        of_kernel = [n for n in calls if f"/{kernel}/" in n]
        assert of_kernel and all("/dtpu.moe_experts/" in n for n in of_kernel), kernel
        for mark in passes:
            assert any(mark in n for n in of_kernel), f"no {kernel} call under {mark}"


class _Loader:
    def __init__(self, batches):
        self.batches = batches

    def set_epoch(self, epoch, start_batch=0):
        pass

    def __len__(self):
        return len(self.batches)

    def __iter__(self):
        return iter(self.batches)


def _journal_of_a_toy_epoch(cfg, tmp_path, steps: int, print_freq: int, **lm_run) -> list[dict]:
    """The records of one epoch of ``steps`` toy batches through `trainer.train_epoch`, journal validated."""
    resilience.reset_run_stats()
    resilience.clear_preemption()
    cfg.OUT_DIR, cfg.TRAIN.PRINT_FREQ, cfg.TRAIN.BATCH_SIZE = str(tmp_path), print_freq, ROWS
    mesh, state, step = _lm_run(cfg, **lm_run)
    obs.start_run(str(tmp_path))
    loader = _Loader([{"tokens": np.asarray(tokens_of(i, SHARE["vocab"]))} for i in range(steps)])
    try:
        trainer.train_epoch(loader, mesh, step, state, 0, jax.random.key(2), True)
    finally:
        obs.end_run()
    journal = obs.journal_path(str(tmp_path))
    assert validate_journal(journal) == []
    return list(read_journal(journal))


def test_window_records_carry_the_routing_counters(fresh_cfg, tmp_path):
    from distribuuuu_tpu.parallel import moe

    windows = [r for r in _journal_of_a_toy_epoch(fresh_cfg, tmp_path, steps=3, print_freq=2) if r["kind"] == "window"]
    assert len(windows) == 2 and all(w["loss"] > 0 for w in windows)
    slots = ROWS * LENGTH * SHARE["top_k"] * SHARE["experts_held"] / SHARE["experts"]  # the expected share
    for w in windows:
        assert 0.3 * slots < w["moe_slots_here"] < 3 * slots
        assert 1.0 <= w["moe_load_max_over_mean"] < SHARE["experts_held"] + 1e-6
        # the rows computed: the slots in whole blocks of one expert, a window's mean step (two-step windows)
        assert w["moe_slots_here"] <= w["moe_rows_here"] <= w["moe_slots_here"] + SHARE["experts_held"] * moe.BLOCK
        assert (2 * w["moe_rows_here"]) % moe.BLOCK == 0


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
def test_counters_record_says_whether_the_checkpoint_policy_was_on(fresh_cfg, tmp_path, remat):
    """One `remat_policy_layers` event a layer traced under the policy (a trace of ``EM*`` holds three, and a
    run may trace its step more than once), in the journal's ``counters`` record of the run; none where
    ``MODEL.REMAT`` is false."""
    records = _journal_of_a_toy_epoch(fresh_cfg, tmp_path, steps=1, print_freq=1, remat=remat)
    (of_run,) = [r for r in records if r["kind"] == "counters" and r["scope"] == "run"]
    count = of_run["counters"].get(token_lm.REMAT_POLICY_EVENT, 0)
    assert (count >= 3 and count % 3 == 0) if remat else count == 0


def test_prefetch_ships_what_the_batch_holds(fresh_cfg):
    from distribuuuu_tpu.data.loader import REPLAY_CONST, prefetch_to_device

    mesh = data_mesh(2)
    batch = {"tokens": np.arange(4 * 9, dtype=np.int32).reshape(4, 9), "zz_extra": np.ones((4,), np.float32),
             REPLAY_CONST: True}
    (first, second) = list(prefetch_to_device(_Loader([batch, batch]), mesh, 2))
    assert set(first) == {"tokens", "zz_extra"} and first["tokens"] is second["tokens"]  # shipped once
    assert first["tokens"].sharding.spec == jax.sharding.PartitionSpec("data", None)
    np.testing.assert_array_equal(first["tokens"], batch["tokens"])


# -- (g) the task's seams ----------------------------------------------------------------------------

def test_blocked_loss_equals_the_whole_vocabulary_at_once():
    hidden = jax.random.normal(jax.random.key(0), (2, 12, 8))
    head = jax.random.normal(jax.random.key(1), (8, 20))
    labels = jax.random.randint(jax.random.key(2), (2, 12), 0, 20)
    logits_of = lambda h: h @ head
    whole = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(hidden @ head), labels[..., None], axis=-1))
    for block in (8, 24, 7):  # 7 does not divide 24 tokens: one block
        got = trainer.next_token_loss(logits_of, hidden, labels, block)
        assert float(got) == pytest.approx(float(whole), rel=1e-6)
    grads = jax.grad(lambda h: trainer.next_token_loss(logits_of, h, labels, 8))(hidden)
    want = jax.grad(lambda h: -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(h @ head), labels[..., None], -1)))(hidden)
    np.testing.assert_allclose(grads, want, rtol=1e-5, atol=1e-7)


def test_a_fourth_task_name_still_raises(fresh_cfg):
    fresh_cfg.TRAIN.TASK = "segment"
    with pytest.raises(ValueError, match="TRAIN.TASK must be one of"):
        trainer._build_cfg_model()
    with pytest.raises(ValueError, match="TRAIN.TASK must be one of"):
        trainer.make_train_step(None, None, data_mesh(1), 5, task="segment")


def test_the_token_model_needs_its_task_and_the_task_its_synthetic_rows(fresh_cfg):
    from distribuuuu_tpu.data.loader import construct_train_loader

    fresh_cfg.MODEL.ARCH, fresh_cfg.MODEL.MODULE = "nemotron_h", "distribuuuu_tpu.models.nemotron_h"
    with pytest.raises(ValueError, match="TRAIN.TASK 'lm'"):
        trainer._build_cfg_model()
    fresh_cfg.TRAIN.TASK = "lm"
    with pytest.raises(ValueError, match="DUMMY_INPUT"):
        construct_train_loader(data_mesh(1))
    fresh_cfg.MODEL.DUMMY_INPUT, fresh_cfg.LM.SEQ_LEN, fresh_cfg.LM.VOCAB, fresh_cfg.TRAIN.BATCH_SIZE = True, 16, 32, 2
    (batch,) = list(construct_train_loader(data_mesh(1)))[:1]
    assert batch["tokens"].shape == (2, 17) and batch["tokens"].max() < 32


@pytest.mark.parametrize("pattern,want", [("EMEMEMEMEM*", (0, 2, 5)), ("M", (0, 1, 1)), ("EM*", (0, 3, 1)), ("MMMM", (0, 1, 4)),
                                         ("MEMEMEM*EMEMEMEM*", (8, 2, 4))])  # the longest stretch: `EM` four times after eight layers
def test_repeated_unit_of_a_pattern(pattern, want):
    assert token_lm.repeated_unit(pattern) == want


def test_shipped_yaml_builds_the_configurations_701m_parameters(fresh_cfg):
    """Shapes only: the published widths with the held shares count 701 M parameters, within 1 %."""
    from distribuuuu_tpu import config

    config.merge_from_file(os.path.join(os.path.dirname(HERE), "config", "nemotron3_super.yaml"))
    model = trainer._build_cfg_model()
    shapes = jax.eval_shape(lambda k: model.init(k, model.dummy_input(0)), jax.random.key(0))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes["params"]))
    assert abs(count - 701e6) <= 0.01 * 701e6, count
    assert set(shapes["batch_stats"]) == {"U0_b_corr"} and shapes["batch_stats"]["U0_b_corr"].shape == (5, 512)
