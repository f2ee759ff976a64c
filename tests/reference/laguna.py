"""Plain reference: the laguna family's language model, float32 `jax.numpy`.

Residual layers, each an attention mixer and then a feed-forward behind a
plain RMSNorm apiece (``x / sqrt(mean(x²) + eps) · w``); the layer by a pattern
string (``D`` full attention and a dense silu-gated feed-forward, ``W``
sliding-window attention and an expert block, ``F`` full attention and an
expert block); token embedding; final norm; untied head; mean next-token
cross-entropy. Written for reading, not for speed: attention is dense with a
mask (``key <= query``, and on window layers ``key > query - window``), the
experts are a loop, one at a time over all tokens, against a 0/1 selection
matrix. Every matrix product at ``highest`` precision. Imports nothing of the
program.

Attention: q, k, v projections over ``kv_heads`` key heads; an RMSNorm a head on
q and on k with its own weight; the rotary embedding (full layers: YaRN on the
first ``rope_share`` of a head, transcribed from ``transformers``'
``_compute_yarn_parameters``, its sines and cosines times the attention factor;
window layers: plain, on the whole head); scores over ``sqrt(head_dim)``; each
head's output times the sigmoid of its own gate ``u · W_g[:, h]``; ``W_o``. The
experts: softmax over all, the ``top_k`` largest renormalised over the choice,
times ``routed_scale``; beside them the shared expert under ``sigmoid(u ·
w_sg)``.

``sizes`` (a dict) gives the pattern, the widths and what is held: with
``experts_held = experts`` it is the uncut model, with a share it is that
chip's part (the held experts' part of the mixture; the attention, the router,
the shared expert and its gate and the dense feed-forward whole).

Parameters are a flat dict: ``embed [V, D]``, ``head [D, V]``, ``norm_f [D]``
and for layer ``i`` under ``L<i>.``: ``norm``, ``post_norm [D]``, the mixer's and
the feed-forward's (`layer_shapes`). The model has no buffers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NORMS = ("norm", "post_norm", "norm_f", "q_norm", "k_norm")


def heads(kind: str, s: dict) -> int:
    return s["window_heads"] if kind == "W" else s["full_heads"]


def layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
    d, hd, h = s["dim"], s["head_dim"], heads(kind, s)
    mixer = {"q": (d, h * hd), "k": (d, s["kv_heads"] * hd), "v": (d, s["kv_heads"] * hd), "gate": (d, h),
             "o": (h * hd, d), "q_norm": (hd,), "k_norm": (hd,)}
    if kind == "D":
        ff = {"ff1": (d, 2 * s["dense_width"]), "ff2": (s["dense_width"], d)}
    else:
        ff = {"router": (d, s["experts"]),
              "w1": (s["experts_held"], d, 2 * s["expert_width"]),  # gate | up
              "w2": (s["experts_held"], s["expert_width"], d),
              "shared1": (d, 2 * s["shared_width"]), "shared2": (s["shared_width"], d), "shared_gate": (d,)}
    return {"norm": (d,), **mixer, "post_norm": (d,), **ff}


def shapes(s: dict) -> dict[str, tuple]:
    out = {"embed": (s["vocab"], s["dim"])}
    for i, kind in enumerate(s["pattern"]):
        out.update({f"L{i}.{k}": v for k, v in layer_shapes(kind, s).items()})
    out.update({"norm_f": (s["dim"],), "head": (s["dim"], s["vocab"])})
    return out


def init(key, s: dict) -> dict[str, jax.Array]:
    """Seeded weights: normal 0.02; the norms' weights 1."""
    return {name: jnp.ones(shape, jnp.float32) if name.split(".")[-1] in NORMS
            else 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            for i, (name, shape) in enumerate(shapes(s).items())}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu_gated(hidden):
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


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


def rotary(x, inv_freq, scale: float = 1.0):
    """``x [B, L, H, hd]``: its first ``2 · len(inv_freq)`` dimensions turn, pairs ``(i, i + n/2)`` by ``position ·
    inv_freq[i]``, sines and cosines times ``scale``; the rest as it is."""
    half = inv_freq.shape[0]
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = scale * jnp.cos(angle)[None, :, None, :], scale * jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def rotate(kind: str, x, s: dict):
    hd = s["head_dim"]
    if kind == "W":
        return rotary(x, s["window_rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    n = int(hd * s["rope_share"])
    inv_freq = yarn_inv_freq(n, s["rope_theta"], s["yarn_factor"], s["yarn_original_positions"], s["yarn_beta_fast"],
                             s["yarn_beta_slow"])
    return rotary(x, inv_freq, s["yarn_attention_factor"])


def attention(kind: str, p: dict, u, s: dict):
    """Gated grouped-query attention, dense with the kind's mask."""
    b, l, _ = u.shape
    h, g, hd = heads(kind, s), s["kv_heads"], s["head_dim"]
    q = rotate(kind, rms_norm(mm(u, p["q"]).reshape(b, l, h, hd), p["q_norm"], s["eps"]), s)
    k = rotate(kind, rms_norm(mm(u, p["k"]).reshape(b, l, g, hd), p["k_norm"], s["eps"]), s)
    k, v = (jnp.repeat(t, h // g, axis=2) for t in (k, mm(u, p["v"]).reshape(b, l, g, hd)))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    gap = jnp.arange(l)[:, None] - jnp.arange(l)[None, :]  # query - key
    mask = (gap >= 0) & ((gap < s["window"]) if kind == "W" else True)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), v, precision=HI)
    out = out * jax.nn.sigmoid(mm(u, p["gate"]))[..., None]
    return mm(out.reshape(b, l, h * hd), p["o"])


def route(p: dict, x, s: dict):
    """``[T, E]`` mixture weights: softmax over all experts, the top-k kept, renormalised over the choice and
    times the routed scale, else 0."""
    probs = jax.nn.softmax(mm(x, p["router"]), axis=-1)
    _, idx = lax.top_k(probs, s["top_k"])
    chosen = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    return s["routed_scale"] * probs * chosen / jnp.sum(probs * chosen, axis=-1, keepdims=True)


def shared_expert(p: dict, x):
    return jax.nn.sigmoid(mm(x, p["shared_gate"][:, None])) * mm(silu_gated(mm(x, p["shared1"])), p["shared2"])


def experts(p: dict, x, s: dict):
    """The expert block: the held experts' part of the mixture, and the shared expert under its gate."""
    b, l, d = x.shape
    x = x.reshape(b * l, d)
    weights = route(p, x, s)
    routed = 0.0
    for e in range(s["experts_held"]):  # one at a time, over all tokens
        routed = routed + weights[:, s["expert_first"] + e, None] * mm(silu_gated(mm(x, p["w1"][e])), p["w2"][e])
    return (routed + shared_expert(p, x)).reshape(b, l, d)


def layer(kind: str, p: dict, h, s: dict):
    h = h + attention(kind, p, rms_norm(h, p["norm"], s["eps"]), s)
    x = rms_norm(h, p["post_norm"], s["eps"])
    return h + (mm(silu_gated(mm(x, p["ff1"])), p["ff2"]) if kind == "D" else experts(p, x, s))


def layer_params(params: dict, i: int) -> dict:
    prefix = f"L{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def loss_fn(params: dict, stats: dict, tokens, s: dict):
    """Mean next-token cross-entropy over rows of ``L + 1`` ids (inputs and labels one leaf shifted)."""
    del stats
    h = params["embed"][tokens[:, :-1]]
    for i, kind in enumerate(s["pattern"]):
        h = layer(kind, layer_params(params, i), h, s)
    logp = jax.nn.log_softmax(mm(rms_norm(h, params["norm_f"], s["eps"]), params["head"]), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
