"""Plain reference: one chip's share of Qwen3-Next-80B-A3B-Instruct (qwen3_next family).

The benchmark's copy of ``tests/reference/qwen3_next.py``: residual layers, each a mixer and then
an expert block behind a zero-centred RMSNorm apiece; ``G`` Gated DeltaNet with the gated delta rule
one `lax.scan` step a position (no chunks, no WY form), ``A`` gated causal grouped-query attention
with a partial rotary embedding, one masked softmax a block of query rows; the experts one at a
time over all tokens against a 0/1 selection matrix, beside the gated shared expert; token
embedding, final norm, untied head, mean next-token cross-entropy; float32 at ``highest`` matmul
precision. It reads every size from the settings (``LM``), so it is the published widths with the
held shares at the cell's sizes and the toy sizes in a rehearsal; what is held (experts,
vocabulary slice) computes that chip's part, as the program does. Imports nothing of the program.

Departures, each on purpose:

- Memory only, not values: a row at a time (`lax.map` over the rows, each rematerialised), each
  layer under ``jax.checkpoint``, the recurrence in stretches of `STRETCH` positions that are
  rematerialised, attention a head and a block of `ROWS` query rows at a time, an expert's part of the
  mixture rematerialised, the logits `TOKENS` tokens at a time, so that four copies of 626 M float32
  parameters (``compare.reference_readings`` holds the weights, their start, a gradient and the next
  one) and one row's float32 activations at 8192 tokens fit a 16 GB chip.
- Compile time only: the repeats of the pattern's unit are a `lax.scan` over leaves that lead with
  the repeats (`groups`), and the experts held a `lax.scan` over their leading axis, so the
  compiler sees one unit and one expert.
- ``loss_fn`` has no settings argument, so ``shapes``/``init`` remember the sizes they were last
  called with (`_SIZES`); every caller makes the weights before it takes a loss.
- ``precision`` other than ``"f32"`` is the control of ``correct`` (``bf16``, ``fp8``: every
  matrix product's operands, and the recurrence's q, k, v, rounded; the router stays float32, as
  the configuration states), or one of `FAULTS` planted in the mathematics (``tools/calibrate.py
  --controls top9,no_renorm,...``): what the comparison must read as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import product, round_to

HI = lax.Precision.HIGHEST
STRETCH = 64   # positions of the recurrence kept at a time; also where ``no_carry`` drops the state: the cell's chunk
ROWS = 1024    # query rows of attention whose scores exist at a time
TOKENS = 2048  # tokens whose logits over the vocabulary exist at a time
A_FLOOR = 1e-4  # of the draw U(0, 16) whose logarithm ``a_log`` starts as
FAULTS = {
    "top9": "the router keeps one expert fewer than the configuration's top-k",
    "no_renorm": "the chosen probabilities not renormalised over the choice",
    "no_decay": "the delta rule without its decay (alpha = 1)",
    "no_beta": "the delta rule without its write strength (beta = 1)",
    "no_carry": f"the delta rule's state not carried across the boundaries of {STRETCH}-position stretches",
    "no_rope": "attention without its rotary embedding",
    "no_gate": "attention without its sigmoid output gate",
    "no_causal": "attention without its causal mask",
}
ZERO_CENTRED = ("norm", "post_norm", "norm_f", "q_norm", "k_norm")
_SIZES: dict | None = None


def sizes(settings: dict) -> dict:
    lm = settings["LM"]
    return {k.lower(): lm[k] for k in lm}


def _layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
    d = s["dim"]
    if kind == "G":
        keys = s["linear_key_heads"] * s["linear_key_dim"]
        values = s["linear_value_heads"] * s["linear_value_dim"]
        mixer = {"in_qkvz": (d, 2 * keys + 2 * values), "in_ba": (d, 2 * s["linear_value_heads"]),
                 "conv_w": (s["conv_kernel"], 2 * keys + values), "a_log": (s["linear_value_heads"],),
                 "dt_bias": (s["linear_value_heads"],), "gnorm": (s["linear_value_dim"],), "out": (values, d)}
    elif kind == "A":
        q, kv = s["attn_heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
        mixer = {"q": (d, 2 * q), "k": (d, kv), "v": (d, kv), "o": (q, d),
                 "q_norm": (s["head_dim"],), "k_norm": (s["head_dim"],)}
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    experts = {"router": (d, s["experts"]),
               "w1": (s["experts_held"], d, 2 * s["expert_width"]),  # gate | up
               "w2": (s["experts_held"], s["expert_width"], d),
               "shared1": (d, 2 * s["shared_width"]), "shared2": (s["shared_width"], d), "shared_gate": (d,)}
    return {"norm": (d,), **mixer, "post_norm": (d,), **experts}


def repeated_unit(pattern: str) -> tuple[int, int]:
    """``(unit length, repeats)``: the unit and count, at least two, that cover most of the pattern from its
    start (``GGGA``: ``G`` three times); ``(len, 1)`` where nothing repeats."""
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
        out.update({f"{prefix}.{k}": lead + v for k, v in _layer_shapes(kind, s).items()})
    out.update({"norm_f": (s["dim"],), "head": (s["dim"], s["vocab"])})
    return out


def init(key, settings: dict) -> dict[str, jax.Array]:
    """Seeded weights: normal 0.02; the zero-centred norms' ``w`` 0; the gated norm's weight and ``dt_bias`` 1;
    ``a_log = log U(0, 16)``, the draw floored at `A_FLOOR`."""
    f32 = jnp.float32
    params = {}
    for i, (name, shape) in enumerate(shapes(settings).items()):
        k = jax.random.fold_in(key, i)
        leaf = name.split(".")[-1]
        if leaf in ZERO_CENTRED:
            params[name] = jnp.zeros(shape, f32)
        elif leaf in ("gnorm", "dt_bias"):
            params[name] = jnp.ones(shape, f32)
        elif leaf == "a_log":
            params[name] = jnp.log(jax.random.uniform(k, shape, f32, A_FLOOR, 16.0))
        else:
            params[name] = 0.02 * jax.random.normal(k, shape, f32)
    return params


def init_stats(settings: dict) -> dict[str, jax.Array]:
    """No buffers: a softmax router has no correction bias."""
    del settings
    return {}


# --------------------------------------------------------------------------
# the program's names for the same leaves (its flax tree)
# --------------------------------------------------------------------------

def _program_name(name: str) -> str:
    return name.replace(".", "_")  # its flat tree: ``L3.q`` is ``L3_q``


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


def _rms_norm(x, w, eps):
    """Zero-centred: ``x / sqrt(mean(x²) + eps) · (1 + w)``."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def _silu_gated(hidden):
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _delta_rule(q, k, v, alpha, beta, carry: bool):
    """``S' = α_t S``; ``S_t = S' + k_t ⊗ β_t (v_t − S'ᵀ k_t)``; ``o_t = S_tᵀ q_t`` over ``[L, H, ·]``, one
    position a step, in rematerialised stretches of `STRETCH` positions where the length allows."""
    length, h, kd = k.shape
    vd = v.shape[-1]

    def step(state, at_t):
        q_t, k_t, v_t, a_t, b_t = at_t
        state = a_t[:, None, None] * state
        written = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t, precision=HI))
        state = state + k_t[:, :, None] * written[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=HI)

    series = (q, k, v, alpha, beta)
    zero = jnp.zeros((h, kd, vd), jnp.float32)
    if length % STRETCH:
        return lax.scan(step, zero, series)[1]

    @jax.checkpoint
    def stretch(state, chunk):
        state, o = lax.scan(step, state, chunk)
        return (state if carry else zero), o

    chunks = jax.tree.map(lambda t: t.reshape(length // STRETCH, STRETCH, *t.shape[1:]), series)
    return lax.scan(stretch, zero, chunks)[1].reshape(length, h, vd)


def _delta_net(p: dict, u, s: dict, precision: str):
    """One row ``u [L, D]``."""
    length = u.shape[0]
    hk, hv, dk, dv = s["linear_key_heads"], s["linear_value_heads"], s["linear_key_dim"], s["linear_value_dim"]
    keys, values = hk * dk, hv * dv
    qkv, z = jnp.split(_mm(u, p["in_qkvz"], precision), (2 * keys + values,), axis=-1)
    beta, a = jnp.split(_mm(u, p["in_ba"], precision), 2, axis=-1)
    padded = jnp.pad(qkv, ((s["conv_kernel"] - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + length] for j in range(s["conv_kernel"])))
    q, k, v = jnp.split(qkv, (keys, 2 * keys), axis=-1)
    unit = lambda t: t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q.reshape(length, hk, dk)) * dk ** -0.5, hv // hk, axis=1)  # a key head's value heads
    k = jnp.repeat(unit(k.reshape(length, hk, dk)), hv // hk, axis=1)
    v = v.reshape(length, hv, dv)
    if precision in ("bf16", "fp8"):  # the recurrence's products take these as operands
        q, k, v = (round_to(t, precision, jnp.float8_e4m3fn) for t in (q, k, v))
    alpha = jnp.exp(-jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"]))
    alpha = jnp.ones_like(alpha) if precision == "no_decay" else alpha
    beta = jnp.ones_like(beta) if precision == "no_beta" else jax.nn.sigmoid(beta)
    o = _delta_rule(q, k, v, alpha, beta, carry=precision != "no_carry")
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + s["norm_eps"]) * p["gnorm"]  # plain weight
    return _mm((o * jax.nn.silu(z.reshape(length, hv, dv))).reshape(length, values), p["out"], precision)


def _rotary(x, share: float, theta: float):
    """``x [L, H, hd]``: the first ``share`` of a head turns, pairs ``(i, i + n/2)`` by ``position · theta^(-2i/n)``."""
    n = int(x.shape[-1] * share)
    half = n // 2
    angle = jnp.arange(x.shape[0])[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:n], x[..., n:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(p: dict, u, s: dict, precision: str):
    length = u.shape[0]
    hq, hkv, hd = s["attn_heads"], s["kv_heads"], s["head_dim"]
    query, gate = jnp.split(_mm(u, p["q"], precision).reshape(length, hq, 2 * hd), 2, axis=-1)
    k, v = (_mm(u, p[name], precision).reshape(length, hkv, hd) for name in "kv")
    query, k = _rms_norm(query, p["q_norm"], s["norm_eps"]), _rms_norm(k, p["k_norm"], s["norm_eps"])
    if precision != "no_rope":
        query, k = (_rotary(t, s["rope_share"], s["rope_theta"]) for t in (query, k))
    k, v = (jnp.repeat(t, hq // hkv, axis=1).transpose(1, 0, 2) for t in (k, v))  # [H, L, hd]
    rows = ROWS if length % ROWS == 0 else length
    query = query.transpose(1, 0, 2).reshape(hq, length // rows, rows, hd)
    starts = jnp.arange(length // rows) * rows

    @jax.checkpoint
    def head(qkv):  # a head, a block of query rows after the other: one masked softmax a row
        q, k, v = qkv

        def block(q_and_start):
            q_block, start = q_and_start
            mask = (jnp.arange(length)[None, :] <= start + jnp.arange(rows)[:, None]) | (precision == "no_causal")
            scores = jnp.where(mask, _mm(q_block, k.T, precision) * hd ** -0.5, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), v, precision)

        return lax.map(jax.checkpoint(block), (q, starts)).reshape(length, hd)

    out = lax.map(head, (query, k, v)).transpose(1, 0, 2)  # [L, H, hd]
    if precision != "no_gate":
        out = out * jax.nn.sigmoid(gate)
    return _mm(out.reshape(length, hq * hd), p["o"], precision)


def _experts(p: dict, x, s: dict, precision: str):
    probs = jax.nn.softmax(jnp.matmul(x, p["router"], precision=HI), axis=-1)  # float32 in every precision
    _, idx = lax.top_k(probs, s["top_k"] - (precision == "top9"))
    chosen = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    weights = probs * chosen
    if precision != "no_renorm":
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    held = weights[:, s["expert_first"]:s["expert_first"] + s["experts_held"]]

    @jax.checkpoint  # what it adds, not the running sum: nothing of an expert is kept for the backward pass
    def its_part(w1, w2, weight):
        return weight[:, None] * _mm(_silu_gated(_mm(x, w1, precision)), w2, precision)

    def one_expert(routed, expert):  # the loop over the experts held, as one body: each over all tokens
        return routed + its_part(*expert), None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(x), (p["w1"], p["w2"], held.T))
    shared = _mm(_silu_gated(_mm(x, p["shared1"], precision)), p["shared2"], precision)
    return routed + jax.nn.sigmoid(jnp.matmul(x, p["shared_gate"][:, None], precision=HI)) * shared


def _layer(kind: str, p: dict, h, s: dict, precision: str):
    u = _rms_norm(h, p["norm"], s["norm_eps"])
    h = h + (_delta_net if kind == "G" else _attention)(p, u, s, precision)
    return h + _experts(p, _rms_norm(h, p["post_norm"], s["norm_eps"]), s, precision)


def _row_loss(params: dict, row, s: dict, precision: str):
    """Summed next-token cross-entropy of one row of ``L + 1`` ids."""
    def leaves(prefix):
        return {k[len(prefix) + 1:]: v for k, v in params.items() if k.startswith(prefix + ".")}

    def one(kind):  # a layer, rematerialised
        return jax.checkpoint(lambda h, p: _layer(kind, p, h, s, precision))

    h = params["embed"][row[:-1]]
    unit = [(prefix, kind) for prefix, kind, repeats in groups(s) if repeats]
    if unit:  # the repeats, one after the other: a scan over the leading axis of the unit's leaves
        def one_unit(h, per_repeat):
            for (_, kind), p in zip(unit, per_repeat):
                h = one(kind)(h, p)
            return h, None

        h, _ = lax.scan(one_unit, h, [leaves(prefix) for prefix, _ in unit])
    for prefix, kind, repeats in groups(s):
        if not repeats:
            h = one(kind)(h, leaves(prefix))
    hidden = _rms_norm(h, params["norm_f"], s["norm_eps"])
    tokens = hidden.shape[0]
    block = TOKENS if tokens % TOKENS == 0 else tokens

    @jax.checkpoint
    def block_nll(hidden_and_labels):  # the vocabulary's logits for a block of tokens at a time
        hidden, labels = hidden_and_labels
        logits = _mm(hidden, params["head"], precision)
        return -jnp.sum(jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), labels[:, None], axis=-1))

    return jnp.sum(lax.map(block_nll, (hidden.reshape(tokens // block, block, -1), row[1:].reshape(tokens // block, block))))


def loss_fn(params, stats, batch, precision: str = "f32"):
    """Mean next-token cross-entropy over the rows of ``batch`` (input kind ``tokens``: ``L + 1`` ids a
    row, inputs and labels one leaf shifted). Returns (loss, stats): the model has no buffers."""
    if _SIZES is None:
        raise RuntimeError("make the weights (shapes/init) before the loss: they carry the sizes")
    if precision not in ("f32", "bf16", "fp8") and precision not in FAULTS:
        raise ValueError(f"precision {precision!r}: f32, a control (bf16, fp8) or a fault of {sorted(FAULTS)}")
    tokens = batch["tokens"]
    one_row = jax.checkpoint(lambda row: _row_loss(params, row, _SIZES, precision))
    return jnp.sum(lax.map(one_row, tokens)) / (tokens.shape[0] * (tokens.shape[1] - 1)), stats
