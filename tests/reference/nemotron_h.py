"""Plain reference: the nemotron_h family's language model, float32 `jax.numpy`.

Pre-norm residual layers, one mixer each, chosen by a pattern string (``M``
Mamba-2, ``*`` causal grouped-query attention, ``E`` latent mixture of experts
with a shared expert); RMSNorm; token embedding; untied head; mean next-token
cross-entropy. Written for reading, not for speed: the state-space recurrence
is one `lax.scan` step a position, attention is dense with a mask, the experts
are a loop over a 0/1 selection matrix. Every matrix product at ``highest``
precision. Imports nothing of the program.

``sizes`` (a dict) gives the pattern, the widths and what is held: with the
published counts it is the uncut model, with a share it is that chip's part
(the partial sums of ``out_proj``, ``o`` and of the held experts' mixture;
router, latent projections and shared expert whole).

Parameters are a flat dict: ``embed [V, D]``, ``head [D, V]``, ``norm_f [D]``
and for layer ``i`` under ``L<i>.``: ``norm [D]`` and the mixer's
(`layer_shapes`). ``stats`` holds the routers' correction buffers
``L<i>.b_corr [E]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def layer_shapes(kind: str, s: dict) -> dict[str, tuple]:
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


def shapes(s: dict) -> dict[str, tuple]:
    out = {"embed": (s["vocab"], s["dim"])}
    for i, kind in enumerate(s["pattern"]):
        out[f"L{i}.norm"] = (s["dim"],)
        out.update({f"L{i}.{k}": v for k, v in layer_shapes(kind, s).items()})
    out.update({"norm_f": (s["dim"],), "head": (s["dim"], s["vocab"])})
    return out


RESIDUAL_OUT = ("out_proj", "o", "w2", "shared2", "up")  # rescale_prenorm_residual


def init(key, s: dict) -> dict[str, jax.Array]:
    """Seeded weights: normal 0.02, the projections back into the stream scaled by 1/sqrt(2·layers_total);
    ``a_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of a log-uniform step in [1e-3, 0.1];
    ``d`` and norm scales 1; the convolution's weight and bias U(±1/sqrt(kernel))."""
    f32 = jnp.float32
    params = {}
    for i, (name, shape) in enumerate(shapes(s).items()):
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


def init_stats(s: dict) -> dict[str, jax.Array]:
    return {f"L{i}.b_corr": jnp.zeros((s["experts"],), jnp.float32)
            for i, kind in enumerate(s["pattern"]) if kind == "E"}


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, scale, eps, groups: int = 1):
    g = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def mamba(p: dict, u, s: dict):
    """``u [B, L, D] -> [B, L, D]``: the Mamba-2 mixer with the recurrence one step a position."""
    b, l, _ = u.shape
    h, pd, g, n = s["mamba_heads"], s["mamba_head_dim"], s["mamba_groups"], s["ssm_state"]
    inner, bc = h * pd, g * n
    z, xbc, dt = jnp.split(mm(u, p["in_proj"]), (inner, 2 * inner + 2 * bc), axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (s["conv_kernel"] - 1, 0), (0, 0)))
    xbc = p["conv_b"] + sum(p["conv_w"][j] * padded[:, j:j + l] for j in range(s["conv_kernel"]))
    x, bm, cm = jnp.split(jax.nn.silu(xbc), (inner, inner + bc), axis=-1)
    x = x.reshape(b, l, h, pd)
    bm, cm = (jnp.repeat(t.reshape(b, l, g, n), h // g, axis=2) for t in (bm, cm))  # a head's group
    dt = jax.nn.softplus(dt + p["dt_bias"])                                        # [B, L, H]
    decay = jnp.exp(dt * -jnp.exp(p["a_log"]))

    def step(state, at_t):                                                         # state [B, H, P, N]
        x_t, b_t, c_t, dt_t, a_t = at_t
        state = a_t[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HI) + p["d"][:, None] * x_t

    time_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = lax.scan(step, jnp.zeros((b, h, pd, n), jnp.float32), tuple(map(time_major, (x, bm, cm, dt, decay))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, l, inner)
    y = rms_norm(y * jax.nn.silu(z), p["gnorm"], s["eps"], groups=g)
    return mm(y, p["out_proj"])


def attention(p: dict, u, s: dict):
    """Causal softmax attention, ``attn_heads / kv_heads`` query heads to a key/value head, dense with a mask."""
    b, l, _ = u.shape
    hq, hkv, hd = s["attn_heads"], s["kv_heads"], s["head_dim"]
    q = mm(u, p["q"]).reshape(b, l, hq, hd)
    k, v = (jnp.repeat(mm(u, p[n]).reshape(b, l, hkv, hd), hq // hkv, axis=2) for n in "kv")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v, precision=HI)
    return mm(out.reshape(b, l, hq * hd), p["o"])


def route(p: dict, b_corr, u, s: dict):
    """``[T, E]`` mixture weights: top-k of ``sigmoid + b_corr``, the chosen scores normalised and scaled, else 0."""
    scores = jax.nn.sigmoid(mm(u, p["router"]))
    _, idx = lax.top_k(scores + b_corr, s["top_k"])
    chosen = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], idx].set(1.0)  # the 0/1 selection
    return s["routed_scale"] * scores * chosen / jnp.sum(scores * chosen, axis=-1, keepdims=True)


def moe(p: dict, b_corr, u, s: dict):
    """Latent mixture of experts: the held experts' part, and router, latent projections, shared expert whole."""
    b, l, d = u.shape
    u = u.reshape(b * l, d)
    weights = route(p, b_corr, u, s)
    latent = mm(u, p["down"])
    mixed = 0.0
    for e in range(s["experts_held"]):
        hidden = jnp.square(jax.nn.relu(mm(latent, p["w1"][e])))
        mixed = mixed + weights[:, s["expert_first"] + e, None] * mm(hidden, p["w2"][e])
    shared = mm(jnp.square(jax.nn.relu(mm(u, p["shared1"]))), p["shared2"])
    return (mm(mixed, p["up"]) + shared).reshape(b, l, d)


def layer(kind: str, p: dict, b_corr, h, s: dict):
    """``h + Mixer(RMSNorm(h))`` with the layer's own leaves ``p`` (its prefix stripped)."""
    u = rms_norm(h, p["norm"], s["eps"])
    if kind == "M":
        return h + mamba(p, u, s)
    if kind == "*":
        return h + attention(p, u, s)
    return h + moe(p, b_corr, u, s)


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
