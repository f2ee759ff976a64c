"""Plain reference: one chip's share of Laguna-S-2.1 (laguna family).

The benchmark's copy of ``tests/reference/laguna.py``: residual layers, each an attention mixer and
then a feed-forward behind a plain RMSNorm apiece; ``D`` full attention and a dense gated
feed-forward, ``W`` sliding-window attention and an expert block, ``F`` full attention and an expert
block. Attention is one dense masked softmax a block of query rows (the mask ``key <= query``, and on
window layers also ``key > query - window``), the query and key norms a head, a rotary embedding (on
full layers YaRN on the first half of a head, transcribed here from the formula of ``transformers``'
``_compute_yarn_parameters``; on window layers plain on the whole head), a sigmoid gate a head on the
output; the experts one at a time over all tokens against a 0/1 selection matrix, their weights the
softmax's chosen probabilities over their sum times the routed scale, beside the gated shared expert;
token embedding, final norm, untied head, mean next-token cross-entropy; float32 at ``highest``
matmul precision. It reads every size from the settings (``LM``), so it is the published widths with
the held shares at the cell's sizes and the toy sizes in a rehearsal; what is held (experts,
vocabulary slice) computes that chip's part, as the program does. Imports nothing of the program.

Departures, each on purpose:

- Memory only, not values: a row at a time (`lax.map` over the rows, each rematerialised), each
  layer under ``jax.checkpoint``, the dense feed-forward `TOKENS` tokens at a time, attention a key
  head's group of query heads at a time (their own columns of ``q``, the gate and ``o``), a head and
  a block of `ROWS` query rows at a time, each rematerialised, an expert's part of the mixture
  rematerialised, the logits `TOKENS` tokens at a time, so that four copies of 811 M float32
  parameters (``compare.reference_readings`` holds the weights, their start, a gradient and the next
  one) and one row's float32 activations at 8192 tokens fit a 16 GB chip.
- Compile time only: the repeats of the pattern's unit (the window layers after the leading dense
  one) are a `lax.scan` over leaves that lead with the repeats (`groups`), and the experts held a
  `lax.scan` over their leading axis, so the compiler sees one unit and one expert.
- ``loss_fn`` has no settings argument, so ``shapes``/``init`` remember the sizes they were last
  called with (`_SIZES`); every caller makes the weights before it takes a loss.
- ``precision`` other than ``"f32"`` is the control of ``correct`` (``bf16``, ``fp8``: every
  matrix product's operands rounded; the router stays float32, as the configuration states), or one
  of `FAULTS` planted in the mathematics (``tools/calibrate.py --controls no_window,no_yarn,...``):
  what the comparison must read as not correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import product

HI = lax.Precision.HIGHEST
ROWS = 1024    # query rows of attention whose scores exist at a time
TOKENS = 2048  # tokens whose feed-forward hidden units, or logits over the vocabulary, exist at a time
FAULTS = {
    "no_window": "window layers causal over the whole prefix",
    "no_yarn": "full layers with a plain rotary embedding in place of YaRN (no ramp, no attention factor)",
    "no_gate": "attention without its sigmoid gate a head",
    "no_scale": "the routed weights not scaled by the routed scale",
}
NORMS = ("norm", "post_norm", "norm_f", "q_norm", "k_norm")
_SIZES: dict | None = None


def sizes(settings: dict) -> dict:
    lm = settings["LM"]
    return {k.lower(): lm[k] for k in lm}


def _heads(kind: str, s: dict) -> int:
    return s["window_heads"] if kind == "W" else s["full_heads"]


def _layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
    d, hd, h = s["dim"], s["head_dim"], _heads(kind, s)
    mixer = {"q": (d, h * hd), "k": (d, s["kv_heads"] * hd), "v": (d, s["kv_heads"] * hd), "gate": (d, h),
             "o": (h * hd, d), "q_norm": (hd,), "k_norm": (hd,)}
    if kind == "D":
        ff = {"ff1": (d, 2 * s["dense_width"]), "ff2": (s["dense_width"], d)}  # gate | up
    elif kind in "WF":
        ff = {"router": (d, s["experts"]),
              "w1": (s["experts_held"], d, 2 * s["expert_width"]),  # gate | up
              "w2": (s["experts_held"], s["expert_width"], d),
              "shared1": (d, 2 * s["shared_width"]), "shared2": (s["shared_width"], d), "shared_gate": (d,)}
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return {"norm": (d,), **mixer, "post_norm": (d,), **ff}


def repeated_unit(pattern: str) -> tuple[int, int, int]:
    """``(first, unit length, repeats)``: the unit and count, at least two, that cover most of the pattern, after
    its first ``first`` layers (``DWWWF``: ``W`` three times after one layer); ``(0, len, 1)`` where nothing
    repeats."""
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
    """Seeded weights: normal 0.02; the norms' weights 1."""
    f32 = jnp.float32
    params = {}
    for i, (name, shape) in enumerate(shapes(settings).items()):
        if name.split(".")[-1] in NORMS:
            params[name] = jnp.ones(shape, f32)
        else:
            params[name] = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape, f32)
    return params


def init_stats(settings: dict) -> dict[str, jax.Array]:
    """No buffers: a softmax router has no correction bias."""
    del settings
    return {}


# --------------------------------------------------------------------------
# the program's names for the same leaves (its flax tree)
# --------------------------------------------------------------------------

def _program_name(name: str) -> str:
    return name.replace(".", "_")  # its flat tree: ``L4.q`` is ``L4_q``


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


def _feed_forward(x, w1, w2, precision: str):
    """``W2 (silu(gate) ⊙ up)`` of ``x [T, D]``, `TOKENS` tokens at a time, each block rematerialised."""
    tokens = x.shape[0]
    block = TOKENS if tokens % TOKENS == 0 else tokens
    one = jax.checkpoint(lambda x: _mm(_silu_gated(_mm(x, w1, precision)), w2, precision))
    return lax.map(one, x.reshape(tokens // block, block, -1)).reshape(tokens, -1)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int, beta_fast: float, beta_slow: float):
    """``transformers``' ``_compute_yarn_parameters`` line for line: the frequencies of ``dim / 2`` pairs."""
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)

    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low)
    inv_freq_extrapolation_factor = 1 - jnp.clip(linear, 0, 1)
    return (inv_freq_interpolation * (1 - inv_freq_extrapolation_factor)
            + inv_freq_extrapolation * inv_freq_extrapolation_factor)


def _rotary(x, inv_freq, scale: float = 1.0):
    """``x [L, H, hd]``: its first ``2 · len(inv_freq)`` dimensions turn, pairs ``(i, i + n/2)`` by ``position ·
    inv_freq[i]``, sines and cosines times ``scale``; the rest as it is."""
    half = inv_freq.shape[0]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = scale * jnp.cos(angle)[:, None, :], scale * jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _rotate(kind: str, x, s: dict, precision: str):
    hd = s["head_dim"]
    if kind == "W":
        return _rotary(x, s["window_rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    n = int(hd * s["rope_share"])
    if precision == "no_yarn":
        return _rotary(x, s["rope_theta"] ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    inv_freq = yarn_inv_freq(n, s["rope_theta"], s["yarn_factor"], s["yarn_original_positions"], s["yarn_beta_fast"],
                             s["yarn_beta_slow"])
    return _rotary(x, inv_freq, s["yarn_attention_factor"])


def _attention(kind: str, p: dict, u, s: dict, precision: str):
    """One row ``u [L, D]``: a key head's group of query heads after the other (their own columns of ``q`` and the
    gate and rows of ``o``), each rematerialised."""
    length = u.shape[0]
    h, g, hd, eps = _heads(kind, s), s["kv_heads"], s["head_dim"], s["norm_eps"]
    per = h // g
    windowed = kind == "W" and precision != "no_window"
    k = _rotate(kind, _rms_norm(_mm(u, p["k"], precision).reshape(length, g, hd), p["k_norm"], eps), s, precision)
    v = _mm(u, p["v"], precision).reshape(length, g, hd)
    gate = jax.nn.sigmoid(_mm(u, p["gate"], precision))  # [L, H]: a head's own
    rows = ROWS if length % ROWS == 0 else length
    starts = jnp.arange(length // rows) * rows

    def head(q, k, v):  # a head, a block of query rows after the other: one masked softmax a row
        def block(q_and_start):
            q_block, start = q_and_start
            gap = start + jnp.arange(rows)[:, None] - jnp.arange(length)[None, :]  # query - key
            mask = (gap >= 0) & ((gap < s["window"]) if windowed else True)
            scores = jnp.where(mask, _mm(q_block, k.T, precision) * hd ** -0.5, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), v, precision)

        return lax.map(jax.checkpoint(block), (q.reshape(length // rows, rows, hd), starts)).reshape(length, hd)

    @jax.checkpoint
    def one_group(acc, i):
        cols = lambda w, width, axis=-1: lax.dynamic_slice_in_dim(w, i * width, width, axis=axis)
        q = _rms_norm(_mm(u, cols(p["q"], per * hd), precision).reshape(length, per, hd), p["q_norm"], eps)
        q = _rotate(kind, q, s, precision).transpose(1, 0, 2)  # [per, L, hd]
        k_i, v_i = (lax.dynamic_index_in_dim(t, i, axis=1, keepdims=False) for t in (k, v))
        out = lax.map(jax.checkpoint(lambda q: head(q, k_i, v_i)), q).transpose(1, 0, 2)  # [L, per, hd]
        if precision != "no_gate":
            out = out * cols(gate, per)[..., None]
        return acc + _mm(out.reshape(length, per * hd), cols(p["o"], per * hd, axis=0), precision), None

    return lax.scan(one_group, jnp.zeros_like(u), jnp.arange(g))[0]


def _experts(p: dict, x, s: dict, precision: str):
    probs = jax.nn.softmax(jnp.matmul(x, p["router"], precision=HI), axis=-1)  # float32 in every precision
    _, idx = lax.top_k(probs, s["top_k"])
    chosen = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    weights = probs * chosen
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if precision != "no_scale":
        weights = weights * s["routed_scale"]
    held = weights[:, s["expert_first"]:s["expert_first"] + s["experts_held"]]

    @jax.checkpoint  # what it adds, not the running sum: nothing of an expert is kept for the backward pass
    def its_part(w1, w2, weight):
        return weight[:, None] * _mm(_silu_gated(_mm(x, w1, precision)), w2, precision)

    def one_expert(routed, expert):  # the loop over the experts held, as one body: each over all tokens
        return routed + its_part(*expert), None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(x), (p["w1"], p["w2"], held.T))
    shared = _feed_forward(x, p["shared1"], p["shared2"], precision)
    return routed + jax.nn.sigmoid(jnp.matmul(x, p["shared_gate"][:, None], precision=HI)) * shared


def _layer(kind: str, p: dict, h, s: dict, precision: str):
    h = h + _attention(kind, p, _rms_norm(h, p["norm"], s["norm_eps"]), s, precision)
    x = _rms_norm(h, p["post_norm"], s["norm_eps"])
    if kind == "D":
        return h + _feed_forward(x, p["ff1"], p["ff2"], precision)
    return h + _experts(p, x, s, precision)


def _row_loss(params: dict, row, s: dict, precision: str):
    """Summed next-token cross-entropy of one row of ``L + 1`` ids."""
    def leaves(prefix):
        return {k[len(prefix) + 1:]: v for k, v in params.items() if k.startswith(prefix + ".")}

    def one(kind):  # a layer, rematerialised
        return jax.checkpoint(lambda h, p: _layer(kind, p, h, s, precision))

    h = params["embed"][row[:-1]]
    unit = [(prefix, kind) for prefix, kind, repeats in groups(s) if repeats]

    def one_unit(h, per_repeat):
        for (_, kind), p in zip(unit, per_repeat):
            h = one(kind)(h, p)
        return h, None

    for prefix, kind, repeats in groups(s):  # in the order the layers run
        if not repeats:
            h = one(kind)(h, leaves(prefix))
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
    row, inputs and labels one leaf shifted). Returns (loss, stats): the model has no buffers."""
    if _SIZES is None:
        raise RuntimeError("make the weights (shapes/init) before the loss: they carry the sizes")
    if precision not in ("f32", "bf16", "fp8") and precision not in FAULTS:
        raise ValueError(f"precision {precision!r}: f32, a control (bf16, fp8) or a fault of {sorted(FAULTS)}")
    tokens = batch["tokens"]
    one_row = jax.checkpoint(lambda row: _row_loss(params, row, _SIZES, precision))
    return jnp.sum(lax.map(one_row, tokens)) / (tokens.shape[0] * (tokens.shape[1] - 1)), stats
