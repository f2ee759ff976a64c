"""Latent-attention / mixture-of-experts language model (the deepseek_v3 family).

A stack of residual layers, each multi-head latent attention and then a
feed-forward, two norms and two residual adds a layer::

    h <- h + Attn(rms(h; w_in))        h <- h + FF(rms(h; w_post))

with the feed-forward chosen by a pattern string: ``D`` a dense gated
feed-forward, ``E`` an expert block (``DEEEE`` is the leading dense layer and
four expert layers). The norm is plain, ``rms(x; w) = x / sqrt(mean(x²) + eps) · w``
with ``w`` starting at 1. Token embedding in, final norm and an untied head
out; no bias anywhere.

- Attention, without a query bottleneck: ``q = u W_q``, a head
  ``[q_n (qk_nope_dim) | q_r (qk_rope_dim)]``; ``[c (kv_latent) | k_r (qk_rope_dim)] = u W_kva``;
  ``ĉ = rms(c; w_c)``; a head's ``[k_n (qk_nope_dim) | v (v_head_dim)] = ĉ W_kvb``.
  The rotary embedding turns ``q_r`` of every head and ``k_r``, which is one
  head that all heads share (`ops.attention.partial_rotary` over the whole of
  those dimensions, pairs ``(i, i + qk_rope_dim/2)``). Scores of head ``i``:
  ``(q_n,i · k_n,i + q_r,i · k_r) / sqrt(qk_nope_dim + qk_rope_dim)``, causal
  softmax, values of ``v_head_dim`` (`ops.attention.latent_causal_attention`,
  under the scope ``dtpu.latent_attn``), then ``W_o``. This is the expanded
  form, a trainer's; the absorbed form over a latent cache is a decoder's.
- ``D``: ``W_down (silu(W_gate x) ⊙ W_up x)`` of ``dense_width``, gate and up
  one first weight side by side.
- ``E``: ``s = sigmoid(x W_r)`` over all experts in float32, the ``top_k``
  largest of ``s + b`` (``b`` a correction buffer of the checkpoint, not
  trained), ``w_i = routed_scale · s_i / Σ_chosen s_j``
  (`parallel.moe.sigmoid_topk_route`); the held experts' part of the mixture,
  each ``W_down (silu(W_gate x) ⊙ W_up x)`` (`parallel.moe.held_experts` with
  `silu_gated` between its products); beside it the shared experts as one
  ungated feed-forward of ``shared_width``.

What a chip may hold its share of is a size (`Sizes`): the experts
``expert_first … expert_first + experts_held - 1`` of ``experts`` and the
``vocab`` rows of embedding and head. Attention with all its heads, the
latent, the router (its ``experts`` outputs and ``top_k``), the shared experts
and a dense layer's feed-forward are whole on every chip of an expert-parallel
layout; widths are never a share.

The stack itself (the embedding, the dense layer on its own and ``EEEE`` as
one `lax.scan` over stacked leaves, the layer checkpoint, the head in blocks,
the routing counters) is `models/token_lm.TokenLM`. With ``remat`` a layer's
checkpoint keeps what `KEPT` names and computes the rest again in the backward
pass.

Float32: parameters, norm statistics, the rotary embedding, the router,
softmax. Matrix products and the residual stream: ``dtype``.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distribuuuu_tpu.models import token_lm
from distribuuuu_tpu.models.registry import register_model
from distribuuuu_tpu.models.token_lm import mixer_proj, mm, rms_norm
from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops.attention import CAUSAL_LSE, CAUSAL_OUT, latent_causal_attention, partial_rotary
from distribuuuu_tpu.parallel.moe import ROUTE_IDX, held_experts, round_rows_for, sigmoid_topk_route, silu_gated

#: what a layer's checkpoint keeps for the backward pass (``remat=True``), each in the dtype it has; everything
#: else of the layer is computed again there
KEPT = (
    "moe_router_logits",  # the router's product at `highest`, the dearest a FLOP: float32, 4 B an expert of the router
    ROUTE_IDX,            # `top_k`'s full sort over the experts: int32, 4 B a chosen expert (named in parallel/moe.py)
    CAUSAL_OUT,           # the causal core's output and, on the kernels' route, the rows' log-sum-exp (float32, 4 B a head
    CAUSAL_LSE,           # and row): with both kept the layer's recomputation never runs the core's forward again, and the
                          # kernels' backward reads them as they are (ops/attention.causal_attention names them)
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size of the model; the counts a chip may hold its share of are ``experts_held`` and ``vocab``."""

    pattern: str              # one letter a layer, what follows its attention: D (dense feed-forward) or E (expert block)
    vocab: int                # rows of embedding and head held (a slice of the vocabulary)
    dim: int
    attn_heads: int
    kv_latent: int            # the normed latent that every head's keys and values are expanded from
    qk_nope_dim: int          # a head's query/key dimensions without position
    qk_rope_dim: int          # ... and its rotary ones; the key's are one head that all heads share
    v_head_dim: int
    rope_theta: float
    dense_width: int
    experts: int              # the router's outputs: all experts of the layer
    experts_held: int         # experts expert_first ... expert_first + experts_held - 1 live here
    expert_first: int
    top_k: int
    expert_width: int
    shared_width: int         # the shared experts as one feed-forward
    routed_scale: float
    eps: float = 1e-6


def layer_shapes(kind: str, s: Sizes) -> dict[str, tuple]:
    """Leaf -> shape of one layer's parameters: its two norms, its attention's and its feed-forward's."""
    d, h = s.dim, s.attn_heads
    mixer = {"q": (d, h * (s.qk_nope_dim + s.qk_rope_dim)),
             "kv_a": (d, s.kv_latent + s.qk_rope_dim),          # the latent | the shared rotary key
             "kv_norm": (s.kv_latent,),
             "kv_b": (s.kv_latent, h * (s.qk_nope_dim + s.v_head_dim)),  # a head's key part, then its value
             "o": (h * s.v_head_dim, d)}
    if kind == "D":
        ff = {"ff1": (d, 2 * s.dense_width), "ff2": (s.dense_width, d)}  # gate | up
    elif kind == "E":
        ff = {"router": (d, s.experts),
              "w1": (s.experts_held, d, 2 * s.expert_width),  # gate | up
              "w2": (s.experts_held, s.expert_width, d),
              "shared1": (d, 2 * s.shared_width), "shared2": (s.shared_width, d)}
    else:
        raise ValueError(f"unknown layer kind {kind!r} in pattern {s.pattern!r}: one of D, E")
    return {"norm": (d,), **mixer, "post_norm": (d,), **ff}


def param_shapes(s: Sizes) -> dict[str, tuple]:
    return token_lm.param_shapes(s, layer_shapes)


def _initializer(name: str, s: Sizes):
    del s
    if token_lm.leaf_of(name) in ("norm", "post_norm", "kv_norm", "norm_f"):
        return nn.initializers.ones
    return nn.initializers.normal(0.02)


# ---------------------------------------------------------------------------
# attention and the two feed-forwards: pure functions of one layer's leaves
# ---------------------------------------------------------------------------

def latent_attention_mixer(p: dict, u, s: Sizes):
    b, l, _ = u.shape
    h, dn, dr, dv = s.attn_heads, s.qk_nope_dim, s.qk_rope_dim, s.v_head_dim
    rotary = lambda t: partial_rotary(t, dr, s.rope_theta)  # float32 in, every dimension given turns
    q_n, q_r = jnp.split(mixer_proj(u, p["q"]).reshape(b, l, h, dn + dr), (dn,), axis=-1)
    q = jnp.concatenate([q_n, rotary(q_r)], axis=-1).astype(u.dtype)
    latent, k_r = jnp.split(mixer_proj(u, p["kv_a"]), (s.kv_latent,), axis=-1)
    k_r = rotary(k_r[:, :, None, :])[:, :, 0].astype(u.dtype)  # one head, which every head reads
    latent = rms_norm(latent, p["kv_norm"], s.eps).astype(u.dtype)
    k_n, v = jnp.split(mixer_proj(latent, p["kv_b"]).astype(u.dtype).reshape(b, l, h, dn + dv), (dn,), axis=-1)
    with step_scope("latent_attn"):
        out = latent_causal_attention(q, k_n, k_r, v)
    return mixer_proj(out, p["o"])


def dense_feed_forward(p: dict, u):
    with step_scope("dense_ffn"):
        return mm(silu_gated(mm(u, p["ff1"])).astype(u.dtype), p["ff2"])


def expert_block(p: dict, b_corr, u32, s: Sizes, dtype):
    """``u32``: the normed stream in float32, which the router reads as it is. Returns the block's output
    (the held experts' part of the mixture and the shared experts) and the held experts' loads."""
    b, l, dim = u32.shape
    u32 = u32.reshape(b * l, dim)
    u = u32.astype(dtype)
    with step_scope("moe_route"):
        logits = checkpoint_name(jnp.dot(u32, p["router"], precision=lax.Precision.HIGHEST), "moe_router_logits")
        idx, weights = sigmoid_topk_route(logits, s.top_k, b_corr, s.routed_scale)  # names its `idx` itself
    rows = round_rows_for(b * l, s.top_k, s.experts, s.experts_held)
    # between an expert's two products stands `silu(gate) ⊙ up`, as in the shared experts below
    mixed, counts = held_experts(u, idx, weights, p["w1"], p["w2"], s.expert_first, rows, between=silu_gated)
    with step_scope("dense_ffn"):
        shared = mm(silu_gated(mm(u, p["shared1"])).astype(dtype), p["shared2"])
    return (mixed + shared).reshape(b, l, dim), counts


def layer(kind: str, p: dict, b_corr, h, s: Sizes):
    """Latent attention and a feed-forward, each behind its norm and added to the stream; also an expert
    layer's loads, else None."""
    h = h + latent_attention_mixer(p, rms_norm(h, p["norm"], s.eps).astype(h.dtype), s).astype(h.dtype)
    u32 = rms_norm(h, p["post_norm"], s.eps)
    if kind == "D":
        out, counts = dense_feed_forward(p, u32.astype(h.dtype)), None
    else:
        out, counts = expert_block(p, b_corr, u32, s, h.dtype)
    return h + out.astype(h.dtype), counts


class DeepseekV3(token_lm.TokenLM):
    layer_shapes = staticmethod(layer_shapes)
    initializer = staticmethod(_initializer)
    layer = staticmethod(layer)
    final_norm = staticmethod(rms_norm)
    kept = KEPT
    buffered = "E"  # the routers' ``e_score_correction_bias``


@register_model("deepseek_v3")
def deepseek_v3(num_classes=None, dtype=jnp.bfloat16, bn_axis_name=None, remat: bool = False,
                norm_eps: float = 1e-6, **sizes):
    """The model of the config's ``LM`` section, which `trainer._build_cfg_model` passes key by key
    in lower case under ``TRAIN.TASK lm``; ``MODEL.MODULE`` names this module to have it registered."""
    del num_classes, bn_axis_name  # a token model has a vocabulary, and no BatchNorm
    if not sizes:
        raise ValueError("MODEL.ARCH 'deepseek_v3' maps token ids to hidden states and is sized "
                         "by the LM section: set TRAIN.TASK 'lm'")
    return DeepseekV3(token_lm.sizes_from(Sizes, dict(sizes, eps=norm_eps)), dtype=dtype, remat=remat)
