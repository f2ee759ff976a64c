"""Hybrid Mamba-2 / attention / latent-expert language model (the nemotron_h family).

A stack of pre-norm residual layers, one mixer each, chosen by a pattern
string: ``M`` a Mamba-2 mixer, ``*`` causal grouped-query attention, ``E`` a
latent mixture of experts with one shared expert. ``h <- h + Mixer(RMSNorm(h))``
with the residual stream in the compute dtype; token embedding in, final
RMSNorm and an untied head out. No bias anywhere but the Mamba convolution's,
no position embedding (the Mamba layers carry order).

Every count a chip may hold its share of is a size (`Sizes`): ``mamba_heads``
with their ``mamba_groups`` of B/C, ``attn_heads`` with ``kv_heads``, the
experts ``expert_first … expert_first + experts_held - 1`` of ``experts``, and
the ``vocab`` rows of embedding and head. With the published counts it is the
whole layer; with a share it computes that chip's partial sum of ``out_proj``,
``o`` and of its experts' mixture (router, latent projections and shared
expert are whole on every chip), which is what goes on to the next layer on
one chip. Widths (``dim``, head sizes, state, latent, expert widths, the
router's ``experts`` outputs and ``top_k``) are never a share.

The parameters are one flat dict (`param_shapes`), the mixers pure functions
of their layer's leaves; the stack itself (the embedding, the repeated unit
``EM`` of ``EMEMEMEMEM*`` as one `lax.scan` over stacked leaves: a third of the
program and half of its compile time, the layer checkpoint, the final norm,
the head in blocks and the routing counters) is `models/token_lm.TokenLM`,
which the token models share.

With ``remat`` every layer is one `jax.checkpoint` under a policy (`KEPT`): the
values it names are stored on the way forward in the dtype they have (the
router's float32 logits and the ids `top_k` chose, so that the routing runs
once a step; the latent projection; the float32 results of the shared expert's
first product and of the Mamba mixer's input projection), and everything else
of the layer is computed again in the backward pass: the norms, `relu²`, the
convolution, `silu` and the scan, attention's q, k, v and its kernel, the held
experts' first product, the comparison that picks the chosen scores and the
gather of the rows. The closing products (``shared2``, ``up``, ``out_proj``,
``o``, the experts' second) never run twice: nothing of the backward pass reads
their results. Without ``remat`` there is no checkpoint at all.

Float32: parameters, RMSNorm statistics, the router (scores, top-k, weights),
softmax, the scan's decays and state (ops/ssm.py). Matrix products and the
residual stream: ``dtype``.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distribuuuu_tpu.models import token_lm
from distribuuuu_tpu.models.registry import register_model
from distribuuuu_tpu.models.token_lm import mm as _mm
from distribuuuu_tpu.models.token_lm import mixer_proj, rms_norm
from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.ops.attention import self_attention
from distribuuuu_tpu.ops.short_conv import causal_conv_silu
from distribuuuu_tpu.ops.ssm import ssd_scan
from distribuuuu_tpu.parallel.moe import (
    ROUTE_IDX, held_experts, relu_squared, round_rows_for, sigmoid_topk_route,
)

F32 = jnp.float32
#: projections back into the residual stream: their init is scaled by 1/sqrt(2·layers_total)
RESIDUAL_OUT = ("out_proj", "o", "w2", "shared2", "up")
#: what a layer's checkpoint keeps for the backward pass (``remat=True``), each in the dtype it has; everything
#: else of the layer is computed again there. One line a name: what keeping it saves, what it costs a token.
#: The list is the longest that left the chip 0.85 GiB at the benchmark's 8192-token step (PERF.md §5, PR 32)
KEPT = (
    "moe_router_logits",  # the router's product at `highest`, the dearest a FLOP: float32, 4 B an expert of the router
    ROUTE_IDX,            # `top_k`'s full sort over the experts: int32, 4 B a chosen expert (named in parallel/moe.py)
    "moe_latent",         # `down`: the compute dtype, one element a latent width
    "moe_shared1",        # the shared expert's first product, the largest of the unit: float32, 4 B a shared width
    "mamba_in_proj",      # the Mamba mixer's input projection: float32, 4 B an element of z | x B C | dt
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size of the model; the counts a chip may hold its share of are the ones held here."""

    pattern: str           # one letter a layer: M, * or E
    vocab: int             # rows of embedding and head held (a slice of the vocabulary)
    dim: int
    layers_total: int      # depth of the whole model: the residual projections' init is scaled by it
    mamba_heads: int       # held, with their mamba_groups of B/C
    mamba_head_dim: int
    mamba_groups: int
    ssm_state: int
    conv_kernel: int
    chunk: int
    attn_heads: int        # held, with their kv_heads
    kv_heads: int
    head_dim: int
    experts: int           # the router's outputs: all experts of the layer
    experts_held: int      # experts expert_first ... expert_first + experts_held - 1 live here
    expert_first: int
    top_k: int
    latent: int
    expert_width: int
    shared_width: int
    routed_scale: float
    eps: float = 1e-5


def layer_shapes(kind: str, s: Sizes) -> dict[str, tuple]:
    """Leaf -> shape of one layer's parameters (its ``norm`` and its mixer's)."""
    d = s.dim
    if kind == "M":
        inner, bc = s.mamba_heads * s.mamba_head_dim, s.mamba_groups * s.ssm_state
        mixer = {"in_proj": (d, 2 * inner + 2 * bc + s.mamba_heads),  # z | x B C | dt
                 "conv_w": (s.conv_kernel, inner + 2 * bc), "conv_b": (inner + 2 * bc,),
                 "dt_bias": (s.mamba_heads,), "a_log": (s.mamba_heads,), "d": (s.mamba_heads,),
                 "gnorm": (inner,), "out_proj": (inner, d)}
    elif kind == "*":
        q, kv = s.attn_heads * s.head_dim, s.kv_heads * s.head_dim
        mixer = {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)}
    elif kind == "E":
        mixer = {"router": (d, s.experts), "down": (d, s.latent),
                 "w1": (s.experts_held, s.latent, s.expert_width),
                 "w2": (s.experts_held, s.expert_width, s.latent),
                 "up": (s.latent, d), "shared1": (d, s.shared_width), "shared2": (s.shared_width, d)}
    else:
        raise ValueError(f"unknown layer kind {kind!r} in pattern {s.pattern!r}: one of M, *, E")
    return {"norm": (d,), **mixer}


def layer_prefixes(s: Sizes) -> list[tuple[str, str, int]]:
    return token_lm.layer_prefixes(s.pattern)


def param_shapes(s: Sizes) -> dict[str, tuple]:
    return token_lm.param_shapes(s, layer_shapes)


def _initializer(name: str, s: Sizes):
    leaf = token_lm.leaf_of(name)
    if leaf in ("norm", "norm_f", "gnorm", "d"):
        return nn.initializers.ones
    if leaf == "a_log":
        return lambda key, shape, dtype=F32: jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))
    if leaf == "dt_bias":  # the inverse softplus of a log-uniform step in [1e-3, 0.1], floored at 1e-4
        def dt_bias(key, shape, dtype=F32):
            dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(0.1)))
            dt = jnp.maximum(dt, 1e-4)
            return dt + jnp.log(-jnp.expm1(-dt))

        return dt_bias
    if leaf in ("conv_w", "conv_b"):  # a depthwise conv1d's default: fan-in is the kernel alone
        bound = s.conv_kernel ** -0.5
        return lambda key, shape, dtype=F32: jax.random.uniform(key, shape, dtype, -bound, bound)
    return nn.initializers.normal(0.02 / math.sqrt(2 * s.layers_total) if leaf in RESIDUAL_OUT else 0.02)


# ---------------------------------------------------------------------------
# the mixers: pure functions of one layer's leaves
# ---------------------------------------------------------------------------

def mamba_mixer(p: dict, u, s: Sizes):
    b, l, _ = u.shape
    inner, bc = s.mamba_heads * s.mamba_head_dim, s.mamba_groups * s.ssm_state
    projected = checkpoint_name(mixer_proj(u, p["in_proj"]), "mamba_in_proj")
    z, xbc, dt = jnp.split(projected, (inner, 2 * inner + 2 * bc), axis=-1)
    xbc = causal_conv_silu(xbc, p["conv_w"], p["conv_b"], out_dtype=u.dtype)  # depthwise over time, then silu
    x, bmat, cmat = jnp.split(xbc, (inner, inner + bc), axis=-1)
    y = ssd_scan(
        x.reshape(b, l, s.mamba_heads, s.mamba_head_dim), jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
        bmat.reshape(b, l, s.mamba_groups, s.ssm_state), cmat.reshape(b, l, s.mamba_groups, s.ssm_state),
        p["d"], s.chunk,
    ).reshape(b, l, inner)
    y = rms_norm(y.astype(F32) * jax.nn.silu(z), p["gnorm"], s.eps, groups=s.mamba_groups)
    return mixer_proj(y.astype(u.dtype), p["out_proj"])


def attention_mixer(p: dict, u, s: Sizes):
    qkv = jnp.concatenate([mixer_proj(u, p[name]).astype(u.dtype) for name in "qkv"], axis=-1)
    return mixer_proj(self_attention(qkv, s.attn_heads, kv_heads=s.kv_heads, causal=True), p["o"])


def moe_mixer(p: dict, b_corr, u32, s: Sizes, dtype):
    """``u32``: the normed stream in float32, which the router reads as it is. Returns the mixture and the
    held experts' loads ``[experts_held]``."""
    b, l, dim = u32.shape
    u32 = u32.reshape(b * l, dim)
    u = u32.astype(dtype)
    with step_scope("moe_route"):
        logits = checkpoint_name(jnp.dot(u32, p["router"], precision=lax.Precision.HIGHEST), "moe_router_logits")
        idx, weights = sigmoid_topk_route(logits, s.top_k, b_corr, s.routed_scale)  # names its `idx` itself
    latent = checkpoint_name(mixer_proj(u, p["down"]).astype(dtype), "moe_latent")
    rows = round_rows_for(b * l, s.top_k, s.experts, s.experts_held)
    # between an expert's two products stands `relu²`, as in the shared expert below
    mixed, counts = held_experts(latent, idx, weights, p["w1"], p["w2"], s.expert_first, rows, between=relu_squared)
    with step_scope("dense_ffn"):
        shared = checkpoint_name(_mm(u, p["shared1"]), "moe_shared1")
        shared = _mm(jnp.square(jax.nn.relu(shared)).astype(dtype), p["shared2"])
    return (mixer_proj(mixed.astype(dtype), p["up"]) + shared).reshape(b, l, dim), counts


def layer(kind: str, p: dict, b_corr, h, s: Sizes):
    """``h + Mixer(RMSNorm(h))`` with the layer's own leaves; also an expert layer's loads, else None."""
    u = rms_norm(h, p["norm"], s.eps)
    counts = None
    if kind == "M":
        out = mamba_mixer(p, u.astype(h.dtype), s)
    elif kind == "*":
        out = attention_mixer(p, u.astype(h.dtype), s)
    else:
        out, counts = moe_mixer(p, b_corr, u, s, h.dtype)
    return h + out.astype(h.dtype), counts


class NemotronH(token_lm.TokenLM):
    layer_shapes = staticmethod(layer_shapes)
    initializer = staticmethod(_initializer)
    layer = staticmethod(layer)
    final_norm = staticmethod(rms_norm)
    kept = KEPT
    buffered = "E"  # the routers' ``e_score_correction_bias``


@register_model("nemotron_h")
def nemotron_h(num_classes=None, dtype=jnp.bfloat16, bn_axis_name=None, remat: bool = False,
               seq_len=None, loss_block=None, norm_eps: float = 1e-5, **sizes):
    """The model of the config's ``LM`` section, which `trainer._build_cfg_model` passes key by key
    in lower case under ``TRAIN.TASK lm``; ``MODEL.MODULE`` names this module to have it registered."""
    del num_classes, bn_axis_name  # a token model has a vocabulary, and no BatchNorm
    del seq_len, loss_block        # the batch's and the loss's, not the model's
    if not sizes:
        raise ValueError("MODEL.ARCH 'nemotron_h' maps token ids to hidden states and is sized "
                         "by the LM section: set TRAIN.TASK 'lm'")
    return NemotronH(token_lm.sizes_from(Sizes, dict(sizes, eps=norm_eps)), dtype=dtype, remat=remat)
