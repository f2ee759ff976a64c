"""Hybrid gated-delta-rule / gated-attention / mixture-of-experts language model (the qwen3_next family).

A stack of residual layers, each a mixer and then an expert block, two norms
and two residual adds a layer::

    h <- h + Mixer(rms(h; w_in))        h <- h + Experts(rms(h; w_post))

with the mixer chosen by a pattern string: ``G`` Gated DeltaNet linear
attention, ``A`` gated softmax attention (``GGGA`` is one period). The norm is
zero-centred, ``rms(x; w) = x / sqrt(mean(x²) + eps) · (1 + w)`` with ``w``
starting at 0. Token embedding in, final norm and an untied head out; no bias
anywhere.

- ``G``: ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``; a depthwise causal
  convolution over time and `silu` on ``[q | k | v]`` (`ops/short_conv.py`); per value head (a key
  head serves ``linear_value_heads / linear_key_heads`` of them) in float32
  ``q <- q/‖q‖ / sqrt(key dim)``, ``k <- k/‖k‖``, ``β = sigmoid(b)``,
  ``log α = −exp(A_log) · softplus(a + dt_bias)``; the gated delta rule
  (`ops/gdn.py`); ``rms(o; w_g) ⊙ silu(z)`` a head (plain weight, starts at
  1), then ``W_out``.
- ``A``: ``[query | gate]`` a head from ``W_q``, ``k``, ``v`` from ``W_k``,
  ``W_v``; the zero-centred norm over a head on query and k; rotary embedding
  on the first ``rope_share`` of a head (`ops.attention.partial_rotary`);
  causal grouped-query attention (`ops.attention.self_attention`, the core
  `nemotron_h` uses); ``⊙ sigmoid(gate)``, then ``W_o``.
- Experts: ``p = softmax(x W_r)`` over all experts in float32, the ``top_k``
  largest, renormalised over the choice (`parallel.moe.softmax_topk_route`);
  the held experts' part of the mixture, each ``W_down (silu(W_gate x) ⊙ W_up x)``
  with gate and up one first weight side by side (`parallel.moe.held_experts`
  with `silu_gated` between its products); beside it one shared expert of the
  same form under ``sigmoid(x · w_sg)``.

What a chip may hold its share of is a size (`Sizes`): the experts
``expert_first … expert_first + experts_held - 1`` of ``experts`` and the
``vocab`` rows of embedding and head. The mixers, the router (its ``experts``
outputs and ``top_k``), the shared expert and its gate are whole on every chip
of an expert-parallel layout; widths are never a share.

The stack itself (the embedding, ``GGG`` of ``GGGA`` as one `lax.scan` over
stacked leaves, the layer checkpoint, the head in blocks, the routing
counters) is `models/token_lm.TokenLM`. With ``remat`` a layer's checkpoint
keeps what `KEPT` names and computes the rest again in the backward pass.

Float32: parameters, norm statistics, the router, softmax, the convolution's
sums, the delta rule's normalisation, decays, triangular inverse and state
(ops/gdn.py), the gates. Matrix products and the residual stream: ``dtype``.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distribuuuu_tpu.models import token_lm
from distribuuuu_tpu.models.registry import register_model
from distribuuuu_tpu.models.token_lm import mixer_proj, mm, rms_norm
from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops.attention import partial_rotary, self_attention
from distribuuuu_tpu.ops.gdn import gated_delta_rule
from distribuuuu_tpu.ops.short_conv import causal_conv_silu
from distribuuuu_tpu.parallel.moe import ROUTE_IDX, held_experts, round_rows_for, silu_gated, softmax_topk_route

F32 = jnp.float32
#: what a layer's checkpoint keeps for the backward pass (``remat=True``), each in the dtype it has; everything
#: else of the layer is computed again there
KEPT = (
    "moe_router_logits",  # the router's product at `highest`, the dearest a FLOP: float32, 4 B an expert of the router
    ROUTE_IDX,            # `top_k`'s full sort over the experts: int32, 4 B a chosen expert (named in parallel/moe.py)
    "gdn_out",            # the delta rule's output: where the rule takes its rows in rematerialised groups (ops/gdn.py) the
                          # layer's recomputation would run its forward pass a third time: the compute dtype, a value width
)
A_FLOOR = 1e-4  # of the draw U(0, 16) whose logarithm `a_log` starts as: no draw is 0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size of the model; the counts a chip may hold its share of are ``experts_held`` and ``vocab``."""

    pattern: str              # one letter a layer: G (gated delta rule) or A (gated attention), each with its expert block
    vocab: int                # rows of embedding and head held (a slice of the vocabulary)
    dim: int
    linear_key_heads: int
    linear_value_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_kernel: int
    chunk: int
    attn_heads: int
    kv_heads: int
    head_dim: int
    rope_share: float         # of a head's dimensions, the first, that the rotary embedding turns
    rope_theta: float
    experts: int              # the router's outputs: all experts of the layer
    experts_held: int         # experts expert_first ... expert_first + experts_held - 1 live here
    expert_first: int
    top_k: int
    expert_width: int
    shared_width: int
    eps: float = 1e-6


def layer_shapes(kind: str, s: Sizes) -> dict[str, tuple]:
    """Leaf -> shape of one layer's parameters: its two norms, its mixer's and its expert block's."""
    d = s.dim
    if kind == "G":
        keys, values = s.linear_key_heads * s.linear_key_dim, s.linear_value_heads * s.linear_value_dim
        mixer = {"in_qkvz": (d, 2 * keys + 2 * values), "in_ba": (d, 2 * s.linear_value_heads),
                 "conv_w": (s.conv_kernel, 2 * keys + values), "a_log": (s.linear_value_heads,),
                 "dt_bias": (s.linear_value_heads,), "gnorm": (s.linear_value_dim,), "out": (values, d)}
    elif kind == "A":
        q, kv = s.attn_heads * s.head_dim, s.kv_heads * s.head_dim
        mixer = {"q": (d, 2 * q), "k": (d, kv), "v": (d, kv), "o": (q, d),  # q: a head's query, then its gate
                 "q_norm": (s.head_dim,), "k_norm": (s.head_dim,)}
    else:
        raise ValueError(f"unknown layer kind {kind!r} in pattern {s.pattern!r}: one of G, A")
    experts = {"router": (d, s.experts),
               "w1": (s.experts_held, d, 2 * s.expert_width),  # gate | up
               "w2": (s.experts_held, s.expert_width, d),
               "shared1": (d, 2 * s.shared_width), "shared2": (s.shared_width, d), "shared_gate": (d,)}
    return {"norm": (d,), **mixer, "post_norm": (d,), **experts}


def param_shapes(s: Sizes) -> dict[str, tuple]:
    return token_lm.param_shapes(s, layer_shapes)


def _initializer(name: str, s: Sizes):
    del s
    leaf = token_lm.leaf_of(name)
    if leaf in ("norm", "post_norm", "norm_f", "q_norm", "k_norm"):  # zero-centred: the scale is 1 + w
        return nn.initializers.zeros
    if leaf == "gnorm" or leaf == "dt_bias":
        return nn.initializers.ones
    if leaf == "a_log":
        return lambda key, shape, dtype=F32: jnp.log(jax.random.uniform(key, shape, dtype, A_FLOOR, 16.0))
    return nn.initializers.normal(0.02)


# ---------------------------------------------------------------------------
# the mixers and the expert block: pure functions of one layer's leaves
# ---------------------------------------------------------------------------

def centred_norm(x, w, eps: float):
    """``x / sqrt(mean(x²) + eps) · (1 + w)`` over the last axis, float32."""
    return rms_norm(x, 1.0 + w.astype(F32), eps)


def delta_mixer(p: dict, u, s: Sizes):
    b, l, _ = u.shape
    hk, hv, dk, dv = s.linear_key_heads, s.linear_value_heads, s.linear_key_dim, s.linear_value_dim
    keys, values = hk * dk, hv * dv
    qkv, z = jnp.split(mixer_proj(u, p["in_qkvz"]).astype(u.dtype), (2 * keys + values,), axis=-1)
    beta, a = jnp.split(mixer_proj(u, p["in_ba"]), 2, axis=-1)             # float32 [B, L, Hv] each
    qkv = causal_conv_silu(qkv, p["conv_w"], out_dtype=F32)  # float32: the unit norms below read it
    q, k, v = jnp.split(qkv, (keys, 2 * keys), axis=-1)
    unit = lambda t: t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q = unit(q.reshape(b, l, hk, dk)) * dk ** -0.5
    k = unit(k.reshape(b, l, hk, dk))
    q, k = (jnp.repeat(t.astype(u.dtype), hv // hk, axis=2) for t in (q, k))  # a key head's value heads
    log_alpha = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = gated_delta_rule(q, k, v.astype(u.dtype).reshape(b, l, hv, dv), log_alpha, jax.nn.sigmoid(beta), s.chunk)
    o = checkpoint_name(o, "gdn_out")
    y = rms_norm(o, p["gnorm"], s.eps) * jax.nn.silu(z.reshape(b, l, hv, dv).astype(F32))
    return mixer_proj(y.astype(u.dtype).reshape(b, l, values), p["out"])


def attention_mixer(p: dict, u, s: Sizes):
    b, l, _ = u.shape
    h, g, hd = s.attn_heads, s.kv_heads, s.head_dim
    query, gate = jnp.split(mixer_proj(u, p["q"]).reshape(b, l, h, 2 * hd), 2, axis=-1)
    k = mixer_proj(u, p["k"]).reshape(b, l, g, hd)
    rotary = lambda t: partial_rotary(t, int(hd * s.rope_share), s.rope_theta)
    query = rotary(centred_norm(query, p["q_norm"], s.eps)).astype(u.dtype)
    k = rotary(centred_norm(k, p["k_norm"], s.eps)).astype(u.dtype)
    qkv = jnp.concatenate([query.reshape(b, l, h * hd), k.reshape(b, l, g * hd), mixer_proj(u, p["v"]).astype(u.dtype)], axis=-1)
    out = self_attention(qkv, h, kv_heads=g, causal=True).astype(F32) * jax.nn.sigmoid(gate.reshape(b, l, h * hd))
    return mixer_proj(out.astype(u.dtype), p["o"])


def expert_block(p: dict, u32, s: Sizes, dtype, routed_scale: float = 1.0):
    """``u32``: the normed stream in float32, which the router reads as it is. Returns the block's output
    (the held experts' part of the mixture, its weights times ``routed_scale``, and the gated shared
    expert) and the held experts' loads."""
    b, l, dim = u32.shape
    u32 = u32.reshape(b * l, dim)
    u = u32.astype(dtype)
    with step_scope("moe_route"):
        logits = checkpoint_name(jnp.dot(u32, p["router"], precision=lax.Precision.HIGHEST), "moe_router_logits")
        idx, weights = softmax_topk_route(logits, s.top_k, routed_scale)  # names its `idx` itself
    rows = round_rows_for(b * l, s.top_k, s.experts, s.experts_held)
    # between an expert's two products stands `silu(gate) ⊙ up`, as in the shared expert below
    mixed, counts = held_experts(u, idx, weights, p["w1"], p["w2"], s.expert_first, rows, between=silu_gated)
    with step_scope("dense_ffn"):
        shared = mm(silu_gated(mm(u, p["shared1"])).astype(dtype), p["shared2"])
        shared = jax.nn.sigmoid(jnp.sum(u32 * p["shared_gate"], axis=-1, keepdims=True)) * shared
    return (mixed + shared).reshape(b, l, dim), counts


def layer(kind: str, p: dict, b_corr, h, s: Sizes):
    """A mixer and an expert block, each behind its norm and added to the stream; also the held experts' loads."""
    del b_corr  # a softmax router has no correction buffer
    u = centred_norm(h, p["norm"], s.eps).astype(h.dtype)
    h = h + (delta_mixer if kind == "G" else attention_mixer)(p, u, s).astype(h.dtype)
    out, counts = expert_block(p, centred_norm(h, p["post_norm"], s.eps), s, h.dtype)
    return h + out.astype(h.dtype), counts


class Qwen3Next(token_lm.TokenLM):
    layer_shapes = staticmethod(layer_shapes)
    initializer = staticmethod(_initializer)
    layer = staticmethod(layer)
    final_norm = staticmethod(centred_norm)
    kept = KEPT


@register_model("qwen3_next")
def qwen3_next(num_classes=None, dtype=jnp.bfloat16, bn_axis_name=None, remat: bool = False,
               norm_eps: float = 1e-6, **sizes):
    """The model of the config's ``LM`` section, which `trainer._build_cfg_model` passes key by key
    in lower case under ``TRAIN.TASK lm``; ``MODEL.MODULE`` names this module to have it registered."""
    del num_classes, bn_axis_name  # a token model has a vocabulary, and no BatchNorm
    if not sizes:
        raise ValueError("MODEL.ARCH 'qwen3_next' maps token ids to hidden states and is sized "
                         "by the LM section: set TRAIN.TASK 'lm'")
    return Qwen3Next(token_lm.sizes_from(Sizes, dict(sizes, eps=norm_eps)), dtype=dtype, remat=remat)
