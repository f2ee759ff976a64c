"""Sliding-window / full attention mixture-of-experts language model (the laguna family).

A stack of residual layers, each an attention mixer and then a feed-forward, two
norms and two residual adds a layer::

    h <- h + Attn(rms(h; w_in))        h <- h + FF(rms(h; w_post))

with the layer chosen by a pattern string: ``D`` full attention and a dense
feed-forward, ``W`` sliding-window attention and an expert block, ``F`` full
attention and an expert block (``DWWWF`` is a leading dense layer, then one
period of three window layers closed by a full one). The norm is plain,
``rms(x; w) = x / sqrt(mean(x²) + eps) · w`` with ``w`` starting at 1. Token
embedding in, final norm and an untied head out; no bias anywhere.

- Attention, both kinds: ``q = u W_q`` a head of ``head_dim``, ``k = u W_k``,
  ``v = u W_v`` over ``kv_heads`` key heads (grouped-query); an RMSNorm a head on
  q and on k with its own weight; a rotary embedding; scores over
  ``sqrt(head_dim)`` in float32 (`ops.attention.causal_attention`); every head's
  output times ``sigmoid(u · W_g[:, h])``, its own gate, then ``W_o``. Full layers
  (``D``, ``F``) have ``full_heads`` query heads, causal over the whole prefix,
  YaRN on the first ``rope_share`` of a head at ``rope_theta``
  (`ops.attention.yarn_inv_freq`, its rotated dimensions scaled by
  ``yarn_attention_factor``); window layers (``W``) have ``window_heads``, row
  ``i`` seeing the keys ``i - window < j <= i`` (the window kernel pair of
  `ops/causal_attention.py`), a plain rotary embedding on a head's every
  dimension at ``window_rope_theta``.
- ``D`` feed-forward: `models.deepseek_v3.dense_feed_forward`, silu-gated.
- ``W``, ``F``: `models.qwen3_next.expert_block`, softmax over all experts in
  float32, the ``top_k`` largest renormalised over the choice and times
  ``routed_scale`` (`parallel.moe.softmax_topk_route`), the held experts'
  silu-gated part of the mixture beside one shared expert under
  ``sigmoid(u · w_sg)``.

What a chip may hold its share of is a size (`Sizes`): the experts
``expert_first … expert_first + experts_held - 1`` of ``experts`` and the
``vocab`` rows of embedding and head. The attention mixers with all their heads,
the router, the shared expert and its gate and the dense feed-forward are whole
on every chip of an expert-parallel layout; widths are never a share.

The stack itself (the embedding, the dense layer on its own, ``WWW`` as one
`lax.scan` over stacked leaves, the layer checkpoint, the head in blocks, the
routing counters) is `models/token_lm.TokenLM`. With ``remat`` a layer's
checkpoint keeps what `KEPT` names and computes the rest again in the backward
pass.

Float32: parameters, norm statistics, the rotary embedding, scores and softmax,
the gates, the router. Matrix products and the residual stream: ``dtype``.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from distribuuuu_tpu.models import deepseek_v3, qwen3_next, token_lm
from distribuuuu_tpu.models.registry import register_model
from distribuuuu_tpu.models.token_lm import mixer_proj, rms_norm
from distribuuuu_tpu.ops.attention import CAUSAL_LSE, CAUSAL_OUT, causal_attention, partial_rotary, yarn_inv_freq
from distribuuuu_tpu.parallel.moe import ROUTE_IDX

F32 = jnp.float32
#: what a layer's checkpoint keeps for the backward pass (``remat=True``), each in the dtype it has; everything
#: else of the layer is computed again there
KEPT = (
    "moe_router_logits",  # the router's product at `highest`, the dearest a FLOP: float32, 4 B an expert of the router
    ROUTE_IDX,            # `top_k`'s full sort over the experts: int32, 4 B a chosen expert (named in parallel/moe.py)
    CAUSAL_OUT,           # the attention core's output and, on the kernels' route, the rows' log-sum-exp, so that the
    CAUSAL_LSE,           # layer's recomputation never runs the core's forward again (as models/deepseek_v3.KEPT)
)
NORMS = ("norm", "post_norm", "norm_f", "q_norm", "k_norm")  # weights that start at 1


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size of the model; the counts a chip may hold its share of are ``experts_held`` and ``vocab``."""

    pattern: str              # one letter a layer: D (full, dense), W (window, experts), F (full, experts)
    vocab: int                # rows of embedding and head held (a slice of the vocabulary)
    dim: int
    full_heads: int           # query heads of a full layer
    window_heads: int         # query heads of a window layer
    kv_heads: int
    head_dim: int
    window: int               # keys a window row sees, its own among them
    rope_share: float         # of a full layer's head, the first dimensions, that its rotary embedding turns
    rope_theta: float
    yarn_factor: float        # the full layers' YaRN: positions stretched this many times over yarn_original_positions
    yarn_original_positions: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_attention_factor: float
    window_rope_theta: float
    dense_width: int
    experts: int              # the router's outputs: all experts of the layer
    experts_held: int         # experts expert_first ... expert_first + experts_held - 1 live here
    expert_first: int
    top_k: int
    expert_width: int
    shared_width: int
    routed_scale: float
    eps: float = 1e-6


def heads_of(kind: str, s: Sizes) -> int:
    return s.window_heads if kind == "W" else s.full_heads


def layer_shapes(kind: str, s: Sizes) -> dict[str, tuple]:
    """Leaf -> shape of one layer's parameters: its two norms, its attention's and its feed-forward's."""
    if kind not in "DWF":
        raise ValueError(f"unknown layer kind {kind!r} in pattern {s.pattern!r}: one of D, W, F")
    d, hd = s.dim, s.head_dim
    h = heads_of(kind, s)
    mixer = {"q": (d, h * hd), "k": (d, s.kv_heads * hd), "v": (d, s.kv_heads * hd), "gate": (d, h),
             "o": (h * hd, d), "q_norm": (hd,), "k_norm": (hd,)}
    if kind == "D":
        ff = {"ff1": (d, 2 * s.dense_width), "ff2": (s.dense_width, d)}  # gate | up
    else:
        ff = {"router": (d, s.experts),
              "w1": (s.experts_held, d, 2 * s.expert_width),  # gate | up
              "w2": (s.experts_held, s.expert_width, d),
              "shared1": (d, 2 * s.shared_width), "shared2": (s.shared_width, d), "shared_gate": (d,)}
    return {"norm": (d,), **mixer, "post_norm": (d,), **ff}


def param_shapes(s: Sizes) -> dict[str, tuple]:
    return token_lm.param_shapes(s, layer_shapes)


def _initializer(name: str, s: Sizes):
    del s
    return nn.initializers.ones if token_lm.leaf_of(name) in NORMS else nn.initializers.normal(0.02)


# ---------------------------------------------------------------------------
# the mixer and the layer: pure functions of one layer's leaves
# ---------------------------------------------------------------------------

def rotary(kind: str, x, s: Sizes):
    """The layer kind's rotary embedding of ``x [B, L, heads, head_dim]`` (float32 in, float32 out)."""
    if kind == "W":
        return partial_rotary(x, s.head_dim, s.window_rope_theta)
    rotary_dim = int(s.head_dim * s.rope_share)
    inv_freq = yarn_inv_freq(rotary_dim, s.rope_theta, s.yarn_factor, s.yarn_original_positions, s.yarn_beta_fast,
                             s.yarn_beta_slow)
    return partial_rotary(x, rotary_dim, s.rope_theta, inv_freq=inv_freq, attention_factor=s.yarn_attention_factor)


def attention_mixer(kind: str, p: dict, u, s: Sizes):
    b, l, _ = u.shape
    h, g, hd = heads_of(kind, s), s.kv_heads, s.head_dim
    q = rms_norm(mixer_proj(u, p["q"]).reshape(b, l, h, hd), p["q_norm"], s.eps)
    k = rms_norm(mixer_proj(u, p["k"]).reshape(b, l, g, hd), p["k_norm"], s.eps)
    v = mixer_proj(u, p["v"]).astype(u.dtype).reshape(b, l, g, hd)
    q, k = (rotary(kind, t, s).astype(u.dtype) for t in (q, k))
    out = causal_attention(q, k, v, window=s.window if kind == "W" else None)  # [B, L, H, hd]
    gate = jax.nn.sigmoid(mixer_proj(u, p["gate"]))                            # float32 [B, L, H]: a head's own
    out = out.astype(F32) * gate[..., None]
    return mixer_proj(out.astype(u.dtype).reshape(b, l, h * hd), p["o"])


def layer(kind: str, p: dict, b_corr, h, s: Sizes):
    """An attention mixer and a feed-forward, each behind its norm and added to the stream; also an expert layer's
    loads, else None."""
    del b_corr  # a softmax router has no correction buffer
    u = rms_norm(h, p["norm"], s.eps).astype(h.dtype)
    h = h + attention_mixer(kind, p, u, s).astype(h.dtype)
    u32 = rms_norm(h, p["post_norm"], s.eps)
    if kind == "D":
        out, counts = deepseek_v3.dense_feed_forward(p, u32.astype(h.dtype)), None
    else:
        out, counts = qwen3_next.expert_block(p, u32, s, h.dtype, routed_scale=s.routed_scale)
    return h + out.astype(h.dtype), counts


class Laguna(token_lm.TokenLM):
    layer_shapes = staticmethod(layer_shapes)
    initializer = staticmethod(_initializer)
    layer = staticmethod(layer)
    final_norm = staticmethod(rms_norm)
    kept = KEPT


@register_model("laguna")
def laguna(num_classes=None, dtype=jnp.bfloat16, bn_axis_name=None, remat: bool = False,
           norm_eps: float = 1e-6, **sizes):
    """The model of the config's ``LM`` section, which `trainer._build_cfg_model` passes key by key
    in lower case under ``TRAIN.TASK lm``; ``MODEL.MODULE`` names this module to have it registered."""
    del num_classes, bn_axis_name  # a token model has a vocabulary, and no BatchNorm
    if not sizes:
        raise ValueError("MODEL.ARCH 'laguna' maps token ids to hidden states and is sized "
                         "by the LM section: set TRAIN.TASK 'lm'")
    return Laguna(token_lm.sizes_from(Sizes, dict(sizes, eps=norm_eps)), dtype=dtype, remat=remat)
