"""Plain reference: the deepseek_v3 family's language model, float32 `jax.numpy`.

Residual layers, each multi-head latent attention and then a feed-forward
behind a plain RMSNorm apiece (``x / sqrt(mean(x²) + eps) · w``); the
feed-forward by a pattern string (``D`` a dense gated feed-forward, ``E`` an
expert block); token embedding; final norm; untied head; mean next-token
cross-entropy. Written for reading, not for speed: attention is the expanded
form straight from the equations, the key formed whole a head (the part
expanded from the latent beside the one rotary key all heads share), dense
with a mask; the experts are a loop, one at a time over all tokens, against a
0/1 selection matrix. Every matrix product at ``highest`` precision. Imports
nothing of the program.

``sizes`` (a dict) gives the pattern, the widths and what is held: with
``experts_held = experts`` it is the uncut model, with a share it is that
chip's part (the held experts' part of the mixture; attention, the router and
its buffer, the shared experts and a dense layer whole).

Parameters are a flat dict: ``embed [V, D]``, ``head [D, V]``, ``norm_f [D]``
and for layer ``i`` under ``L<i>.``: ``norm``, ``post_norm [D]``, attention's
and the feed-forward's (`layer_shapes`). ``stats`` holds the routers'
correction buffers ``L<i>.b_corr [experts]``, which no gradient trains.

The order of the columns of ``q`` (a head's 128 without position, then its
64 rotary), of ``kv_a`` (the latent, then the rotary key) and of ``kv_b`` (a
head's key part, then its value) is this file's, and the rotary pairs are
``(i, i + 32)`` of the 64; the published checkpoint interleaves the pairs
(``rope_interleave``), which seeded weights cannot tell apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NORMS = ("norm", "post_norm", "kv_norm", "norm_f")


def layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
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


def init_stats(s: dict) -> dict[str, jax.Array]:
    """The routers' correction buffers: zero at the start."""
    return {f"L{i}.b_corr": jnp.zeros((s["experts"],), jnp.float32) for i, kind in enumerate(s["pattern"]) if kind == "E"}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def silu_gated(hidden):
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


def rotary(x, theta: float, first_position: int = 0):
    """Rotary embedding on every dimension of each head of ``x [B, L, H, n]``: pairs ``(i, i + n/2)`` turn by
    ``position · theta^(-2i/n)``."""
    half = x.shape[-1] // 2
    angle = (first_position + jnp.arange(x.shape[1]))[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def latent_parts(p: dict, u, s: dict, first_position: int = 0):
    """What the equations give before the scores: ``q_n, q_r [B, L, H, ·]`` (``q_r`` turned), the normed latent
    ``ĉ [B, L, latent]``, the turned rotary key ``k_r [B, L, rope]``, and a head's ``k_n``, ``v [B, L, H, ·]``."""
    b, l, _ = u.shape
    h, dn, dr, dv = s["attn_heads"], s["qk_nope_dim"], s["qk_rope_dim"], s["v_head_dim"]
    q_n, q_r = jnp.split(mm(u, p["q"]).reshape(b, l, h, dn + dr), (dn,), axis=-1)
    latent, k_r = jnp.split(mm(u, p["kv_a"]), (s["kv_latent"],), axis=-1)
    latent = rms_norm(latent, p["kv_norm"], s["eps"])
    k_n, v = jnp.split(mm(latent, p["kv_b"]).reshape(b, l, h, dn + dv), (dn,), axis=-1)
    return q_n, rotary(q_r, s["rope_theta"], first_position), latent, rotary(k_r[:, :, None], s["rope_theta"], first_position)[:, :, 0], k_n, v


def whole_head_attention(q, k, v):
    """Causal softmax attention, dense with a mask: ``q, k [B, L, H, dk]``, ``v [B, L, H, dv]`` -> ``[B, L, H, dv]``;
    the scale is that of the query/key width."""
    l = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * q.shape[-1] ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v, precision=HI)


def attention(p: dict, u, s: dict, first_position: int = 0):
    """Multi-head latent attention, expanded: the key whole a head, ``[k_n,i | k_r]``, the rotary part the same
    for every head."""
    b, l, _ = u.shape
    q_n, q_r, _, k_r, k_n, v = latent_parts(p, u, s, first_position)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r[:, :, None, :], (*k_n.shape[:-1], k_r.shape[-1]))], axis=-1)
    out = whole_head_attention(jnp.concatenate([q_n, q_r], axis=-1), k, v)
    return mm(out.reshape(b, l, -1), p["o"])


def route(p: dict, b_corr, x, s: dict):
    """``[T, E]`` mixture weights: sigmoid scores, the top-k of score + buffer kept, the kept scores normalised
    over the choice and scaled, else 0."""
    scores = jax.nn.sigmoid(mm(x, p["router"]))
    _, idx = lax.top_k(scores + b_corr, s["top_k"])
    chosen = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    return s["routed_scale"] * scores * chosen / (jnp.sum(scores * chosen, axis=-1, keepdims=True) + 1e-20)


def shared_experts(p: dict, x):
    return mm(silu_gated(mm(x, p["shared1"])), p["shared2"])


def experts(p: dict, b_corr, x, s: dict):
    """The expert block: the held experts' part of the mixture, and the shared experts (ungated)."""
    b, l, d = x.shape
    x = x.reshape(b * l, d)
    weights = route(p, b_corr, x, s)
    routed = 0.0
    for e in range(s["experts_held"]):  # one at a time, over all tokens
        routed = routed + weights[:, s["expert_first"] + e, None] * mm(silu_gated(mm(x, p["w1"][e])), p["w2"][e])
    return (routed + shared_experts(p, x)).reshape(b, l, d)


def layer(kind: str, p: dict, b_corr, h, s: dict):
    """``h + Attn(rms(h))``, then ``h + FF(rms(h))``, with the layer's own leaves ``p`` (its prefix stripped)."""
    h = h + attention(p, rms_norm(h, p["norm"], s["eps"]), s)
    x = rms_norm(h, p["post_norm"], s["eps"])
    return h + (mm(silu_gated(mm(x, p["ff1"])), p["ff2"]) if kind == "D" else experts(p, b_corr, x, s))


def layer_params(params: dict, i: int) -> dict:
    prefix = f"L{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def logits_fn(params: dict, stats: dict, tokens, s: dict):
    h = params["embed"][tokens]
    for i, kind in enumerate(s["pattern"]):
        h = layer(kind, layer_params(params, i), stats.get(f"L{i}.b_corr"), h, s)
    return mm(rms_norm(h, params["norm_f"], s["eps"]), params["head"])


def loss_fn(params: dict, stats: dict, tokens, s: dict):
    """Mean next-token cross-entropy over rows of ``L + 1`` ids (inputs and labels one leaf shifted)."""
    logp = jax.nn.log_softmax(logits_fn(params, stats, tokens[:, :-1], s), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
