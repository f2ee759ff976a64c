"""Plain reference: one chip's share of NVIDIA-Nemotron-3-Super-120B-A12B (nemotron_h family).

The benchmark's copy of ``tests/reference/nemotron_h.py``: pre-norm residual layers, one mixer
each by the pattern string (``M`` Mamba-2 with the recurrence one `lax.scan` step a position,
``*`` causal grouped-query attention, dense with a mask, ``E`` a latent mixture of experts as a
loop over a 0/1 selection matrix, with its shared expert), RMSNorm, token embedding, untied head,
mean next-token cross-entropy; float32 at ``highest`` matmul precision. It reads every size from
the settings (``LM``), so it is the published widths with the held shares at the cell's sizes and
the toy sizes in a rehearsal; what is held (heads, experts, vocabulary slice) computes that
chip's partial sums, as the program does. Imports nothing of the program.

Departures, each on purpose:

- Memory only, not values: a row at a time (`lax.map` over the rows, each rematerialised), each
  layer under ``jax.checkpoint``, the recurrence in stretches of `STRETCH` positions that are
  rematerialised, and attention a head at a time, so that 701 M float32 parameters, their
  gradient and one row's float32 activations at 8192 tokens fit a 16 GB chip.
- Compile time only: the repeats of the pattern's unit are a `lax.scan` over leaves that lead with
  the repeats (`groups`), and the experts held a `lax.scan` over their leading axis, so the
  compiler sees one unit and one expert (a run has minutes, and a float32 product at ``highest``
  is six passes of code).
- ``loss_fn`` has no settings argument, so ``shapes``/``init`` remember the sizes they were last
  called with (`_SIZES`); every caller makes the weights before it takes a loss.
- ``precision`` other than ``"f32"`` is the control of ``correct`` (``bf16``, ``fp8``: every
  matrix product's operands, and the recurrence's x, B, C, rounded; the router stays float32, as
  the configuration states), or one of `FAULTS` planted in the mathematics (``tools/calibrate.py
  --controls top21,no_scale,no_carry,no_causal``): what the comparison must read as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import product, round_to

HI = lax.Precision.HIGHEST
STRETCH = 128  # positions of the recurrence kept at a time; also where ``no_carry`` drops the state
FAULTS = {
    "top21": "the router keeps one expert fewer than the configuration's top-k",
    "no_scale": "the routed scaling factor left out",
    "no_carry": f"the scan's state not carried across the boundaries of {STRETCH}-position chunks",
    "no_causal": "attention without its causal mask",
}
RESIDUAL_OUT = ("out_proj", "o", "w2", "shared2", "up")  # rescale_prenorm_residual
_SIZES: dict | None = None


def sizes(settings: dict) -> dict:
    lm = settings["LM"]
    return {k.lower(): lm[k] for k in lm}


def _layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
    d = s["dim"]
    if kind == "M":
        inner, bc = s["mamba_heads"] * s["mamba_head_dim"], s["mamba_groups"] * s["ssm_state"]
        return {"in_proj": (d, 2 * inner + 2 * bc + s["mamba_heads"]),  # z | x B C | dt
                "conv_w": (s["conv_kernel"], inner + 2 * bc), "conv_b": (inner + 2 * bc,),
                "dt_bias": (s["mamba_heads"],), "a_log": (s["mamba_heads"],), "d": (s["mamba_heads"],),
                "gnorm": (inner,), "out_proj": (inner, d)}
    if kind == "*":
        q, kv = s["attn_heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
        return {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)}
    if kind == "E":
        return {"router": (d, s["experts"]), "down": (d, s["latent"]),
                "w1": (s["experts_held"], s["latent"], s["expert_width"]),
                "w2": (s["experts_held"], s["expert_width"], s["latent"]),
                "up": (s["latent"], d), "shared1": (d, s["shared_width"]), "shared2": (s["shared_width"], d)}
    raise ValueError(f"unknown layer kind {kind!r}")


def repeated_unit(pattern: str) -> tuple[int, int]:
    """``(unit length, repeats)``: the unit and count, at least two, that cover most of the pattern from its
    start (``EMEMEMEMEM*``: ``EM`` five times); ``(len, 1)`` where nothing repeats."""
    best, covered = (len(pattern), 1), 0
    for k in range(1, len(pattern) // 2 + 1):
        r = 1
        while pattern[r * k:(r + 1) * k] == pattern[:k]:
            r += 1
        if r >= 2 and k * r > covered:
            best, covered = (k, r), k * r
    return best


def groups(s: dict) -> list[tuple[str, str, int]]:
    """``(prefix, kind, repeats)`` of every group of leaves. The repeats of the pattern's unit are one leaf
    with the repeats leading, as a model whose layers are scanned holds them: ``U<j>`` is layer ``j`` of
    the unit; the layers after the repeats are ``L<i>`` (``repeats`` 0: no such axis). A leaf is the block
    the optimizer's per-leaf measures see, so the reference holds the leaves as the model does."""
    unit, repeats = repeated_unit(s["pattern"])
    scanned = unit * repeats if repeats > 1 else 0
    return ([(f"U{j}", s["pattern"][j], repeats) for j in range(unit if scanned else 0)]
            + [(f"L{i}", s["pattern"][i], 0) for i in range(scanned, len(s["pattern"]))])


def shapes(settings: dict) -> dict[str, tuple]:
    """Flat name -> shape of every trainable leaf (``settings``: the keys merged into the program's ``cfg``)."""
    global _SIZES
    s = _SIZES = sizes(settings)
    out = {"embed": (s["vocab"], s["dim"])}
    for prefix, kind, repeats in groups(s):
        lead = (repeats,) if repeats else ()
        out[f"{prefix}.norm"] = lead + (s["dim"],)
        out.update({f"{prefix}.{k}": lead + v for k, v in _layer_shapes(kind, s).items()})
    out.update({"norm_f": (s["dim"],), "head": (s["dim"], s["vocab"])})
    return out


def init(key, settings: dict) -> dict[str, jax.Array]:
    """Seeded weights: normal 0.02, the projections back into the stream scaled by 1/sqrt(2·LAYERS_TOTAL);
    ``a_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of a log-uniform step in [1e-3, 0.1] floored
    at 1e-4; ``d`` and norm scales 1; the convolution's weight and bias U(±1/sqrt(kernel))."""
    f32 = jnp.float32
    all_shapes = shapes(settings)
    s = _SIZES
    params = {}
    for i, (name, shape) in enumerate(all_shapes.items()):
        k = jax.random.fold_in(key, i)
        leaf = name.split(".")[-1]
        if leaf in ("norm", "norm_f", "gnorm", "d"):
            params[name] = jnp.ones(shape, f32)
        elif leaf == "a_log":
            params[name] = jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        elif leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, f32, jnp.log(1e-3), jnp.log(0.1)))
            dt = jnp.maximum(dt, 1e-4)
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif leaf in ("conv_w", "conv_b"):
            bound = s["conv_kernel"] ** -0.5
            params[name] = jax.random.uniform(k, shape, f32, -bound, bound)
        else:
            std = 0.02 / (2 * s["layers_total"]) ** 0.5 if leaf in RESIDUAL_OUT else 0.02
            params[name] = std * jax.random.normal(k, shape, f32)
    return params


def init_stats(settings: dict) -> dict[str, jax.Array]:
    """The routers' correction buffers: zero, and not trained."""
    s = sizes(settings)
    return {f"{prefix}.b_corr": jnp.zeros(((repeats,) if repeats else ()) + (s["experts"],), jnp.float32)
            for prefix, kind, repeats in groups(s) if kind == "E"}


# --------------------------------------------------------------------------
# the program's names for the same leaves (its flax tree)
# --------------------------------------------------------------------------

def _program_name(name: str) -> str:
    return name.replace(".", "_")  # its flat tree: ``L3.in_proj`` is ``L3_in_proj``


def to_program(params: dict, stats: dict) -> tuple[dict, dict]:
    return ({_program_name(k): v for k, v in params.items()}, {_program_name(k): v for k, v in stats.items()})


def from_program(tree: dict, names) -> dict:
    return {name: tree[_program_name(name)] for name in names}


def compare_leaves(flat: dict) -> dict:
    """Every leaf as it is: no leaf packs parameters that should be read apart."""
    return flat


# --------------------------------------------------------------------------
# forward, loss
# --------------------------------------------------------------------------

def _mm(a, b, precision: str):
    """A matrix product in the control's precision; a planted fault computes in float32."""
    rounding = precision if precision in ("bf16", "fp8") else "f32"
    return product(lambda a, b: jnp.matmul(a, b, precision=HI), a, b, rounding)


def _rms_norm(x, scale, eps, groups: int = 1):
    g = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def _recurrence(x, bm, cm, dt, decay, d_skip, carry: bool):
    """``S_t = a_t S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D x_t`` over ``[L, H, ·]``, one position a step,
    in rematerialised stretches of `STRETCH` positions where the length allows."""
    length, h, pd = x.shape
    n = bm.shape[-1]

    def step(state, at_t):
        x_t, b_t, c_t, dt_t, a_t = at_t
        state = a_t[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HI) + d_skip[:, None] * x_t

    series = (x, bm, cm, dt, decay)
    zero = jnp.zeros((h, pd, n), jnp.float32)
    if length % STRETCH:
        return lax.scan(step, zero, series)[1]

    @jax.checkpoint
    def stretch(state, chunk):
        state, y = lax.scan(step, state, chunk)
        return (state if carry else zero), y

    chunks = jax.tree.map(lambda t: t.reshape(length // STRETCH, STRETCH, *t.shape[1:]), series)
    return lax.scan(stretch, zero, chunks)[1].reshape(length, h, pd)


def _mamba(p: dict, u, s: dict, precision: str):
    """One row ``u [L, D]``."""
    length = u.shape[0]
    h, pd, g, n = s["mamba_heads"], s["mamba_head_dim"], s["mamba_groups"], s["ssm_state"]
    inner, bc = h * pd, g * n
    z, xbc, dt = jnp.split(_mm(u, p["in_proj"], precision), (inner, 2 * inner + 2 * bc), axis=-1)
    padded = jnp.pad(xbc, ((s["conv_kernel"] - 1, 0), (0, 0)))
    xbc = p["conv_b"] + sum(p["conv_w"][j] * padded[j:j + length] for j in range(s["conv_kernel"]))
    x, bm, cm = jnp.split(jax.nn.silu(xbc), (inner, inner + bc), axis=-1)
    if precision in ("bf16", "fp8"):  # the scan's products take these as operands
        x, bm, cm = (round_to(t, precision, jnp.float8_e4m3fn) for t in (x, bm, cm))
    x = x.reshape(length, h, pd)
    bm, cm = (jnp.repeat(t.reshape(length, g, n), h // g, axis=1) for t in (bm, cm))  # a head's group
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, bm, cm, dt, jnp.exp(dt * -jnp.exp(p["a_log"])), p["d"], carry=precision != "no_carry")
    y = _rms_norm(y.reshape(length, inner) * jax.nn.silu(z), p["gnorm"], s["norm_eps"], groups=g)
    return _mm(y, p["out_proj"], precision)


def _attention(p: dict, u, s: dict, precision: str):
    length = u.shape[0]
    hq, hkv, hd = s["attn_heads"], s["kv_heads"], s["head_dim"]
    q = _mm(u, p["q"], precision).reshape(length, hq, hd).transpose(1, 0, 2)
    k, v = (jnp.repeat(_mm(u, p[name], precision).reshape(length, hkv, hd), hq // hkv, axis=1).transpose(1, 0, 2)
            for name in "kv")
    mask = jnp.tril(jnp.ones((length, length), bool)) | (precision == "no_causal")

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv
        scores = jnp.where(mask, _mm(q, k.T, precision) * hd ** -0.5, -jnp.inf)
        return _mm(jax.nn.softmax(scores, axis=-1), v, precision)

    out = lax.map(head, (q, k, v)).transpose(1, 0, 2).reshape(length, hq * hd)
    return _mm(out, p["o"], precision)


def _moe(p: dict, b_corr, u, s: dict, precision: str):
    scores = jax.nn.sigmoid(jnp.matmul(u, p["router"], precision=HI))  # float32 in every precision
    _, idx = lax.top_k(scores + b_corr, s["top_k"] - (precision == "top21"))
    chosen = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    scale = 1.0 if precision == "no_scale" else s["routed_scale"]
    weights = scale * scores * chosen / jnp.sum(scores * chosen, axis=-1, keepdims=True)
    latent = _mm(u, p["down"], precision)
    held = weights[:, s["expert_first"]:s["expert_first"] + s["experts_held"]]

    def one_expert(mixed, expert):  # the loop over the experts held, as one body
        w1, w2, weight = expert
        hidden = jnp.square(jax.nn.relu(_mm(latent, w1, precision)))
        return mixed + weight[:, None] * _mm(hidden, w2, precision), None

    mixed, _ = lax.scan(one_expert, jnp.zeros_like(latent), (p["w1"], p["w2"], held.T))
    shared = _mm(jnp.square(jax.nn.relu(_mm(u, p["shared1"], precision))), p["shared2"], precision)
    return _mm(mixed, p["up"], precision) + shared


def _layer(kind: str, p: dict, b_corr, h, s: dict, precision: str):
    u = _rms_norm(h, p["norm"], s["norm_eps"])
    if kind == "M":
        return h + _mamba(p, u, s, precision)
    if kind == "*":
        return h + _attention(p, u, s, precision)
    return h + _moe(p, b_corr, u, s, precision)


def _row_loss(params: dict, stats: dict, row, s: dict, precision: str):
    """Summed next-token cross-entropy of one row of ``L + 1`` ids."""
    def leaves(prefix):
        own = {k[len(prefix) + 1:]: v for k, v in params.items() if k.startswith(prefix + ".")}
        return own, stats.get(prefix + ".b_corr")

    def one(kind):  # a layer, rematerialised
        return jax.checkpoint(lambda h, p, b: _layer(kind, p, b, h, s, precision))

    h = params["embed"][row[:-1]]
    unit = [(prefix, kind) for prefix, kind, repeats in groups(s) if repeats]
    if unit:  # the repeats, one after the other: a scan over the leading axis of the unit's leaves
        def one_unit(h, per_repeat):
            for (_, kind), (p, b) in zip(unit, per_repeat):
                h = one(kind)(h, p, b)
            return h, None

        h, _ = lax.scan(one_unit, h, [leaves(prefix) for prefix, _ in unit])
    for prefix, kind, repeats in groups(s):
        if not repeats:
            h = one(kind)(h, *leaves(prefix))
    logits = _mm(_rms_norm(h, params["norm_f"], s["norm_eps"]), params["head"], precision)
    return -jnp.sum(jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), row[1:, None], axis=-1))


def loss_fn(params, stats, batch, precision: str = "f32"):
    """Mean next-token cross-entropy over the rows of ``batch`` (input kind ``tokens``: ``L + 1`` ids a
    row, inputs and labels one leaf shifted). Returns (loss, stats): the buffers are not trained."""
    if _SIZES is None:
        raise RuntimeError("make the weights (shapes/init) before the loss: they carry the sizes")
    if precision not in ("f32", "bf16", "fp8") and precision not in FAULTS:
        raise ValueError(f"precision {precision!r}: f32, a control (bf16, fp8) or a fault of {sorted(FAULTS)}")
    tokens = batch["tokens"]
    one_row = jax.checkpoint(lambda row: _row_loss(params, stats, row, _SIZES, precision))
    return jnp.sum(lax.map(one_row, tokens)) / (tokens.shape[0] * (tokens.shape[1] - 1)), stats
