"""Plain reference: one chip's share of kanana-2-30b-a3b-instruct-2601 (deepseek_v3 family).

The benchmark's copy of ``tests/reference/deepseek_v3.py``: residual layers, each multi-head latent
attention and then a feed-forward behind a plain RMSNorm apiece; attention in its expanded form
straight from the equations (the key whole a head: the part expanded from the normed latent beside
the one rotary key that all heads share; one masked softmax a block of query rows); ``D`` a dense
gated feed-forward, ``E`` the experts one at a time over all tokens against a 0/1 selection matrix
(sigmoid scores, the top-k of score plus correction buffer, normalised over the choice and scaled)
beside the shared experts; token embedding, final norm, untied head, mean next-token
cross-entropy; float32 at ``highest`` matmul precision. It reads every size from the settings
(``LM``), so it is the published widths with the held shares at the cell's sizes and the toy sizes
in a rehearsal; what is held (experts, vocabulary slice) computes that chip's part, as the program
does. Imports nothing of the program.

Departures, each on purpose:

- Memory only, not values: a row at a time (`lax.map` over the rows, each rematerialised), each
  layer under ``jax.checkpoint``, attention a head and a block of `ROWS` query rows at a time, an
  expert's part of the mixture rematerialised, the logits `TOKENS` tokens at a time, so that four
  copies of 576 M float32 parameters (``compare.reference_readings`` holds the weights, their start,
  a gradient and the next one) and one row's float32 activations at 8192 tokens fit a 16 GB chip.
- Compile time only: the repeats of the pattern's unit (the expert layers after the leading dense
  one) are a `lax.scan` over leaves that lead with the repeats (`groups`), and the experts held a
  `lax.scan` over their leading axis, so the compiler sees one unit and one expert.
- ``loss_fn`` has no settings argument, so ``shapes``/``init`` remember the sizes they were last
  called with (`_SIZES`); every caller makes the weights before it takes a loss.
- ``precision`` other than ``"f32"`` is the control of ``correct`` (``bf16``, ``fp8``: every
  matrix product's operands rounded; the router stays float32, as the configuration states), or one
  of `FAULTS` planted in the mathematics (``tools/calibrate.py --controls top5,no_scale,...``): what
  the comparison must read as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import product

HI = lax.Precision.HIGHEST
ROWS = 1024   # query rows of attention whose scores exist at a time
TOKENS = 2048  # tokens whose logits over the vocabulary exist at a time
FAULTS = {
    "top5": "the router keeps one expert fewer than the configuration's top-k",
    "no_scale": "the mixture weights without the routed scaling factor (2.448 -> 1)",
    "no_renorm": "the chosen scores not normalised over the choice",
    "no_shared": "the expert block without its shared experts",
    "no_rope": "attention without its rotary embedding",
    "scale_128": "attention's scores over the root of the width without position (128), not of the whole key (192)",
    "no_latent_norm": "keys and values expanded from the latent as projected, without its norm",
    "no_causal": "attention without its causal mask",
}
NORMS = ("norm", "post_norm", "kv_norm", "norm_f")
_SIZES: dict | None = None


def sizes(settings: dict) -> dict:
    lm = settings["LM"]
    return {k.lower(): lm[k] for k in lm}


def _layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
    d, h = s["dim"], s["attn_heads"]
    mixer = {"q": (d, h * (s["qk_nope_dim"] + s["qk_rope_dim"])),
             "kv_a": (d, s["kv_latent"] + s["qk_rope_dim"]), "kv_norm": (s["kv_latent"],),
             "kv_b": (s["kv_latent"], h * (s["qk_nope_dim"] + s["v_head_dim"])), "o": (h * s["v_head_dim"], d)}
    if kind == "D":
        ff = {"ff1": (d, 2 * s["dense_width"]), "ff2": (s["dense_width"], d)}  # gate | up
    elif kind == "E":
        ff = {"router": (d, s["experts"]),
              "w1": (s["experts_held"], d, 2 * s["expert_width"]),  # gate | up
              "w2": (s["experts_held"], s["expert_width"], d),
              "shared1": (d, 2 * s["shared_width"]), "shared2": (s["shared_width"], d)}
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return {"norm": (d,), **mixer, "post_norm": (d,), **ff}


def repeated_unit(pattern: str) -> tuple[int, int, int]:
    """``(first, unit length, repeats)``: the unit and count, at least two, that cover most of the pattern, after
    its first ``first`` layers (``DEEEE``: ``E`` four times after one layer); ``(0, len, 1)`` where nothing repeats."""
    best, covered = (0, len(pattern), 1), 0
    for first in range(len(pattern)):
        for k in range(1, (len(pattern) - first) // 2 + 1):
            r = 1
            while pattern[first + r * k:first + (r + 1) * k] == pattern[first:first + k]:
                r += 1
            if r >= 2 and k * r > covered:
                best, covered = (first, k, r), k * r
    return best


def groups(s: dict) -> list[tuple[str, str, int]]:
    """``(prefix, kind, repeats)`` of every group of leaves, in the order the layers run. The repeats of the
    pattern's unit are one leaf with the repeats leading, as a model whose layers are scanned holds them:
    ``U<j>`` is layer ``j`` of the unit; the layers before and after the repeats are ``L<i>`` (``repeats`` 0: no
    such axis). A leaf is the block the optimizer's per-leaf measures see, so the reference holds the leaves as
    the model does."""
    pattern = s["pattern"]
    first, unit, repeats = repeated_unit(pattern)
    scanned = unit * repeats if repeats > 1 else 0
    single = lambda layers: [(f"L{i}", pattern[i], 0) for i in layers]
    return (single(range(first)) + [(f"U{j}", pattern[first + j], repeats) for j in range(unit if scanned else 0)]
            + single(range(first + scanned, len(pattern))))


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
    """Seeded weights: normal 0.02 on every matrix; the norms' weights 1."""
    f32 = jnp.float32
    return {name: jnp.ones(shape, f32) if name.split(".")[-1] in NORMS
            else 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape, f32)
            for i, (name, shape) in enumerate(shapes(settings).items())}


def init_stats(settings: dict) -> dict[str, jax.Array]:
    """The routers' correction buffers (``e_score_correction_bias``), zero at the start: buffers of the
    checkpoint, which no gradient trains."""
    s = sizes(settings)
    return {f"{prefix}.b_corr": jnp.zeros(((repeats,) if repeats else ()) + (s["experts"],), jnp.float32)
            for prefix, kind, repeats in groups(s) if kind == "E"}


# --------------------------------------------------------------------------
# the program's names for the same leaves (its flax tree)
# --------------------------------------------------------------------------

def _program_name(name: str) -> str:
    return name.replace(".", "_")  # its flat tree: ``U0.kv_b`` is ``U0_kv_b``


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
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _silu_gated(hidden):
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _rotary(x, theta: float):
    """``x [L, H, n]``: every dimension turns, pairs ``(i, i + n/2)`` by ``position · theta^(-2i/n)``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0])[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p: dict, u, s: dict, precision: str):
    """One row ``u [L, D]``: the expanded form, the key whole a head."""
    length = u.shape[0]
    h, dn, dr, dv = s["attn_heads"], s["qk_nope_dim"], s["qk_rope_dim"], s["v_head_dim"]
    q_n, q_r = jnp.split(_mm(u, p["q"], precision).reshape(length, h, dn + dr), (dn,), axis=-1)
    latent, k_r = jnp.split(_mm(u, p["kv_a"], precision), (s["kv_latent"],), axis=-1)
    if precision != "no_latent_norm":
        latent = _rms_norm(latent, p["kv_norm"], s["norm_eps"])
    k_n, v = jnp.split(_mm(latent, p["kv_b"], precision).reshape(length, h, dn + dv), (dn,), axis=-1)
    k_r = k_r[:, None, :]  # one head
    if precision != "no_rope":
        q_r, k_r = _rotary(q_r, s["rope_theta"]), _rotary(k_r, s["rope_theta"])
    query = jnp.concatenate([q_n, q_r], axis=-1).transpose(1, 0, 2)                                   # [H, L, dn + dr]
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (length, h, dr))], axis=-1).transpose(1, 0, 2)   # the shared part to each
    v = v.transpose(1, 0, 2)
    scale = (dn if precision == "scale_128" else dn + dr) ** -0.5
    rows = ROWS if length % ROWS == 0 else length
    query = query.reshape(h, length // rows, rows, dn + dr)
    starts = jnp.arange(length // rows) * rows

    @jax.checkpoint
    def head(qkv):  # a head, a block of query rows after the other: one masked softmax a row
        q, k, v = qkv

        def block(q_and_start):
            q_block, start = q_and_start
            mask = (jnp.arange(length)[None, :] <= start + jnp.arange(rows)[:, None]) | (precision == "no_causal")
            scores = jnp.where(mask, _mm(q_block, k.T, precision) * scale, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), v, precision)

        return lax.map(jax.checkpoint(block), (q, starts)).reshape(length, dv)

    out = lax.map(head, (query, k, v)).transpose(1, 0, 2)  # [L, H, dv]
    return _mm(out.reshape(length, h * dv), p["o"], precision)


def _experts(p: dict, b_corr, x, s: dict, precision: str):
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HI))  # float32 in every precision
    _, idx = lax.top_k(scores + b_corr, s["top_k"] - (precision == "top5"))
    chosen = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    weights = scores * chosen
    if precision != "no_renorm":
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if precision != "no_scale":
        weights = s["routed_scale"] * weights
    held = weights[:, s["expert_first"]:s["expert_first"] + s["experts_held"]]

    @jax.checkpoint  # what it adds, not the running sum: nothing of an expert is kept for the backward pass
    def its_part(w1, w2, weight):
        return weight[:, None] * _mm(_silu_gated(_mm(x, w1, precision)), w2, precision)

    def one_expert(routed, expert):  # the loop over the experts held, as one body: each over all tokens
        return routed + its_part(*expert), None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(x), (p["w1"], p["w2"], held.T))
    if precision == "no_shared":
        return routed
    return routed + _mm(_silu_gated(_mm(x, p["shared1"], precision)), p["shared2"], precision)


def _layer(kind: str, p: dict, b_corr, h, s: dict, precision: str):
    h = h + _attention(p, _rms_norm(h, p["norm"], s["norm_eps"]), s, precision)
    x = _rms_norm(h, p["post_norm"], s["norm_eps"])
    if kind == "D":
        return h + _mm(_silu_gated(_mm(x, p["ff1"], precision)), p["ff2"], precision)
    return h + _experts(p, b_corr, x, s, precision)


def _row_loss(params: dict, stats: dict, row, s: dict, precision: str):
    """Summed next-token cross-entropy of one row of ``L + 1`` ids."""
    def leaves(prefix):
        own = {k[len(prefix) + 1:]: v for k, v in params.items() if k.startswith(prefix + ".")}
        return own, stats.get(prefix + ".b_corr")

    def one(kind):  # a layer, rematerialised
        return jax.checkpoint(lambda h, p, b: _layer(kind, p, b, h, s, precision))

    h = params["embed"][row[:-1]]
    unit = [(prefix, kind) for prefix, kind, repeats in groups(s) if repeats]

    def one_unit(h, per_repeat):
        for (_, kind), (p, b) in zip(unit, per_repeat):
            h = one(kind)(h, p, b)
        return h, None

    for prefix, kind, repeats in groups(s):  # in the order the layers run
        if not repeats:
            h = one(kind)(h, *leaves(prefix))
        elif prefix == unit[0][0]:  # the repeats, one after the other: a scan over the leading axis of the unit's leaves
            h, _ = lax.scan(one_unit, h, [leaves(name) for name, _ in unit])
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
    row, inputs and labels one leaf shifted). Returns (loss, stats): the buffers are not trained."""
    if _SIZES is None:
        raise RuntimeError("make the weights (shapes/init) before the loss: they carry the sizes")
    if precision not in ("f32", "bf16", "fp8") and precision not in FAULTS:
        raise ValueError(f"precision {precision!r}: f32, a control (bf16, fp8) or a fault of {sorted(FAULTS)}")
    tokens = batch["tokens"]
    one_row = jax.checkpoint(lambda row: _row_loss(params, stats, row, _SIZES, precision))
    return jnp.sum(lax.map(one_row, tokens)) / (tokens.shape[0] * (tokens.shape[1] - 1)), stats
