"""Plain reference: the qwen3_next family's language model, float32 `jax.numpy`.

Residual layers, each a mixer and then an expert block behind a zero-centred
RMSNorm apiece (``x / sqrt(mean(x²) + eps) · (1 + w)``); the mixer by a pattern
string (``G`` Gated DeltaNet linear attention, ``A`` gated softmax attention
with a partial rotary embedding); token embedding; final norm; untied head;
mean next-token cross-entropy. Written for reading, not for speed: the gated
delta rule is one `lax.scan` step a position (no chunks, no WY form), attention
is dense with a mask, the experts are a loop, one at a time over all tokens,
against a 0/1 selection matrix. Every matrix product at ``highest`` precision.
Imports nothing of the program.

``sizes`` (a dict) gives the pattern, the widths and what is held: with
``experts_held = experts`` it is the uncut model, with a share it is that
chip's part (the held experts' part of the mixture; router, shared expert and
its gate, and the mixers whole).

Parameters are a flat dict: ``embed [V, D]``, ``head [D, V]``, ``norm_f [D]``
and for layer ``i`` under ``L<i>.``: ``norm``, ``post_norm [D]``, the mixer's and
the expert block's (`layer_shapes`). The model has no buffers (``stats`` is
empty: a softmax router has no correction bias).

The order of the columns of ``in_qkvz`` (``q | k | v | z``, heads in order) and
``in_ba`` (``b | a``) and of ``q`` (a head's query, then its gate) is this
file's; the published checkpoint interleaves them by key head, which seeded
weights cannot tell apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
A_FLOOR = 1e-4  # of the draw U(0, 16) whose logarithm ``a_log`` starts as


def layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
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


def shapes(s: dict) -> dict[str, tuple]:
    out = {"embed": (s["vocab"], s["dim"])}
    for i, kind in enumerate(s["pattern"]):
        out.update({f"L{i}.{k}": v for k, v in layer_shapes(kind, s).items()})
    out.update({"norm_f": (s["dim"],), "head": (s["dim"], s["vocab"])})
    return out


def init_leaf(key, leaf: str, shape) -> jax.Array:
    """Seeded weights: normal 0.02; the zero-centred norms' ``w`` 0; the gated norm's weight and ``dt_bias`` 1;
    ``a_log = log U(0, 16)``, the draw floored at `A_FLOOR`."""
    f32 = jnp.float32
    if leaf in ("norm", "post_norm", "norm_f", "q_norm", "k_norm"):
        return jnp.zeros(shape, f32)
    if leaf in ("gnorm", "dt_bias"):
        return jnp.ones(shape, f32)
    if leaf == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, A_FLOOR, 16.0))
    return 0.02 * jax.random.normal(key, shape, f32)


def init(key, s: dict) -> dict[str, jax.Array]:
    return {name: init_leaf(jax.random.fold_in(key, i), name.split(".")[-1], shape)
            for i, (name, shape) in enumerate(shapes(s).items())}


def init_stats(s: dict) -> dict[str, jax.Array]:
    del s
    return {}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    """Zero-centred: ``x / sqrt(mean(x²) + eps) · (1 + w)``."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def silu_gated(hidden):
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


def delta_rule(q, k, v, alpha, beta):
    """``S' = α_t S``; ``S_t = S' + k_t ⊗ β_t (v_t − S'ᵀ k_t)``; ``o_t = S_tᵀ q_t``, one position a step.
    ``q, k [B, L, H, K]``, ``v [B, L, H, V]``, ``alpha, beta [B, L, H]``; the state ``[B, H, K, V]`` starts at 0."""
    b, _, h, kd = k.shape

    def step(state, at_t):
        q_t, k_t, v_t, a_t, b_t = at_t
        state = a_t[..., None, None] * state
        written = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI))
        state = state + k_t[..., :, None] * written[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    time_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, o = lax.scan(step, jnp.zeros((b, h, kd, v.shape[-1]), jnp.float32), tuple(map(time_major, (q, k, v, alpha, beta))))
    return jnp.moveaxis(o, 0, 1)


def delta_net(p: dict, u, s: dict):
    """``u [B, L, D] -> [B, L, D]``: the Gated DeltaNet mixer."""
    b, l, _ = u.shape
    hk, hv, dk, dv = s["linear_key_heads"], s["linear_value_heads"], s["linear_key_dim"], s["linear_value_dim"]
    keys, values = hk * dk, hv * dv
    qkv, z = jnp.split(mm(u, p["in_qkvz"]), (2 * keys + values,), axis=-1)
    beta, a = jnp.split(mm(u, p["in_ba"]), 2, axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (s["conv_kernel"] - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][j] * padded[:, j:j + l] for j in range(s["conv_kernel"])))
    q, k, v = jnp.split(qkv, (keys, 2 * keys), axis=-1)
    unit = lambda t: t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q.reshape(b, l, hk, dk)) * dk ** -0.5, hv // hk, axis=2)  # a key head's value heads
    k = jnp.repeat(unit(k.reshape(b, l, hk, dk)), hv // hk, axis=2)
    alpha = jnp.exp(-jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"]))
    o = delta_rule(q, k, v.reshape(b, l, hv, dv), alpha, jax.nn.sigmoid(beta))
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + s["eps"]) * p["gnorm"]  # plain weight, a head
    return mm((o * jax.nn.silu(z.reshape(b, l, hv, dv))).reshape(b, l, values), p["out"])


def rotary(x, share: float, theta: float, first_position: int = 0):
    """Rotary embedding on the first ``share`` of each head of ``x [B, L, H, hd]``: pairs ``(i, i + n/2)`` of those
    ``n`` dimensions turn by ``position · theta^(-2i/n)``."""
    n = int(x.shape[-1] * share)
    half = n // 2
    angle = (first_position + jnp.arange(x.shape[1]))[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:n], x[..., n:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(p: dict, u, s: dict, first_position: int = 0):
    """Gated causal softmax attention, ``attn_heads / kv_heads`` query heads to a key/value head, dense with a mask."""
    b, l, _ = u.shape
    hq, hkv, hd = s["attn_heads"], s["kv_heads"], s["head_dim"]
    query, gate = jnp.split(mm(u, p["q"]).reshape(b, l, hq, 2 * hd), 2, axis=-1)
    k, v = (mm(u, p[n]).reshape(b, l, hkv, hd) for n in "kv")
    query = rotary(rms_norm(query, p["q_norm"], s["eps"]), s["rope_share"], s["rope_theta"], first_position)
    k = rotary(rms_norm(k, p["k_norm"], s["eps"]), s["rope_share"], s["rope_theta"], first_position)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", query, k, precision=HI) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v, precision=HI)
    return mm((out * jax.nn.sigmoid(gate)).reshape(b, l, hq * hd), p["o"])


def route(p: dict, x, s: dict):
    """``[T, E]`` mixture weights: softmax over all experts, the top-k kept and renormalised over the choice, else 0."""
    probs = jax.nn.softmax(mm(x, p["router"]), axis=-1)
    _, idx = lax.top_k(probs, s["top_k"])
    chosen = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    return probs * chosen / jnp.sum(probs * chosen, axis=-1, keepdims=True)


def experts(p: dict, x, s: dict):
    """The expert block: the held experts' part of the mixture, and the shared expert under its gate."""
    b, l, d = x.shape
    x = x.reshape(b * l, d)
    weights = route(p, x, s)
    routed = 0.0
    for e in range(s["experts_held"]):  # one at a time, over all tokens
        routed = routed + weights[:, s["expert_first"] + e, None] * mm(silu_gated(mm(x, p["w1"][e])), p["w2"][e])
    shared = jax.nn.sigmoid(mm(x, p["shared_gate"][:, None])) * mm(silu_gated(mm(x, p["shared1"])), p["shared2"])
    return (routed + shared).reshape(b, l, d)


def layer(kind: str, p: dict, h, s: dict):
    """``h + Mixer(rms(h))``, then ``h + Experts(rms(h))``, with the layer's own leaves ``p`` (its prefix stripped)."""
    u = rms_norm(h, p["norm"], s["eps"])
    h = h + (delta_net(p, u, s) if kind == "G" else attention(p, u, s))
    return h + experts(p, rms_norm(h, p["post_norm"], s["eps"]), s)


def layer_params(params: dict, i: int) -> dict:
    prefix = f"L{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def logits_fn(params: dict, stats: dict, tokens, s: dict):
    del stats
    h = params["embed"][tokens]
    for i, kind in enumerate(s["pattern"]):
        h = layer(kind, layer_params(params, i), h, s)
    return mm(rms_norm(h, params["norm_f"], s["eps"]), params["head"])


def loss_fn(params: dict, stats: dict, tokens, s: dict):
    """Mean next-token cross-entropy over rows of ``L + 1`` ids (inputs and labels one leaf shifted)."""
    logp = jax.nn.log_softmax(logits_fn(params, stats, tokens[:, :-1], s), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
