"""Vision Transformer — beyond-reference model family, Flax/TPU-first.

The reference zoo is CNN-only; ViT is included here because it is the
flagship consumer of the framework's transformer machinery (the same
attention math the long-context parallelism in `parallel/ring_attention.py`
/ `parallel/ulysses.py` shards) and the standard large-batch-LAMB workload
(`optim.py`'s 16k-32k regime was published on exactly this family).

Layout matches torchvision's ``vit_b_16`` parameterization (conv patch
embed with bias, learned class token + position table, pre-LN encoder
blocks with packed-qkv attention and GELU MLP, final LN, linear head) so
the parameter inventory is pinnable against well-known totals
(86 567 656 for B/16, 22 050 664 for S/16 — `tests/test_models_vit.py`);
the implementation is fresh jnp/Flax, not a port.

TPU notes:
- matmuls (qkv/proj/mlp, and attention einsums) run in the model compute
  ``dtype`` (bf16 default) — all MXU-shaped ([B·L, D]×[D, kD] with D a
  multiple of 128 for S/B/L variants).
- LayerNorms compute AND emit float32 (they are cheap VPU work on [B,L,D];
  keeping the residual stream's norm boundaries in f32 costs ~nothing and
  preserves the stability the f32-params/bf16-compute convention targets);
  the next matmul casts back down.
- softmax in float32 (``preferred_element_type``), like the rest of the zoo.
- the attention core (scores, softmax, weighted values and their gradients)
  is `ops.attention.self_attention`: on TPUs one fused forward and one fused
  backward kernel that read the packed qkv activation as the ``qkv`` Dense
  leaves it and keep every L×L tensor in VMEM; the same einsums as ever on
  other devices and for tiles VMEM cannot hold. Device and shape decide.
- no data-dependent control flow; blocks unroll at trace time;
  ``MODEL.REMAT`` wraps each encoder block in `jax.checkpoint`.
- the encoder is position-agnostic (positions enter once, at embed time),
  which is exactly what makes it shardable over a sequence axis: see
  `encode_tokens` + `tests/test_models_vit.py::test_vit_encoder_ring_parallel`.

There is no BatchNorm anywhere, so ``bn_axis_name`` is accepted for the
`build_model` contract (`trainer.py:_build_cfg_model`) and ignored.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distribuuuu_tpu.models.layers import maybe_remat
from distribuuuu_tpu.models.registry import register_model

# timm/ViT-paper convention for embedding tables and the torch-MHA-style
# xavier for projection weights.
trunc_normal_02 = nn.initializers.truncated_normal(stddev=0.02)
xavier_uniform = nn.initializers.xavier_uniform()


class MultiHeadSelfAttention(nn.Module):
    """Packed-qkv MHSA. Optionally sequence-parallel: with ``seq_axis`` set
    (inside `shard_map`, tokens sharded over that mesh axis) the score/value
    contraction runs as ring or Ulysses attention instead of dense."""

    num_heads: int
    dtype: Any = jnp.bfloat16
    seq_axis: str | None = None
    seq_impl: str = "ring"  # 'ring' | 'ulysses'

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, l, d = x.shape
        qkv = nn.Dense(
            3 * d, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=xavier_uniform, name="qkv",
        )(x)
        if self.seq_axis is None:
            from distribuuuu_tpu.ops.attention import self_attention

            # fused forward+backward pair or the einsums, by device and shape
            out = self_attention(qkv, self.num_heads)
        else:
            from distribuuuu_tpu.parallel.seq import seq_attention

            qkv = qkv.reshape(b, l, 3, self.num_heads, d // self.num_heads)
            q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))  # [B,H,L,hd]
            # MODEL.SEQ_ATTN routes here; scales internally
            out = seq_attention(q, k, v, impl=self.seq_impl, axis_name=self.seq_axis)
            out = out.transpose(0, 2, 1, 3).reshape(b, l, d)
        return nn.Dense(
            d, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=xavier_uniform, name="proj",
        )(out)


def _layer_norm(name: str) -> nn.LayerNorm:
    # f32 in, f32 out: the norm boundary stays full-precision (module note).
    return nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, param_dtype=jnp.float32, name=name)


class EncoderBlock(nn.Module):
    """Pre-LN transformer block: x + MHSA(LN(x)); x + MLP(LN(x))."""

    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    seq_axis: str | None = None
    seq_impl: str = "ring"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        d = x.shape[-1]
        h = _layer_norm("ln1")(x.astype(jnp.float32))
        h = MultiHeadSelfAttention(
            self.num_heads, dtype=self.dtype,
            seq_axis=self.seq_axis, seq_impl=self.seq_impl, name="attn",
        )(h.astype(self.dtype))
        x = x + h.astype(x.dtype)
        h = _layer_norm("ln2")(x.astype(jnp.float32))
        h = nn.Dense(
            self.mlp_dim, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=xavier_uniform, name="fc1",
        )(h.astype(self.dtype))
        h = nn.gelu(h, approximate=False)  # exact erf-GELU (torchvision parity)
        h = nn.Dense(
            d, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=xavier_uniform, name="fc2",
        )(h)
        return x + h.astype(x.dtype)


class ViT(nn.Module):
    """ViT classifier (patch embed → encoder → head).

    ``pool='token'`` (default) matches torchvision: a learned class token
    carries the representation. ``pool='gap'`` mean-pools patch tokens —
    required for the sequence-parallel encoder path, where a broadcast
    class token has no single home shard.
    """

    patch: int = 16
    dim: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    num_classes: int = 1000
    pool: str = "token"  # 'token' | 'gap'
    dtype: Any = jnp.bfloat16
    remat: bool = False
    bn_axis_name: str | None = None  # no BN in ViT; build_model contract only
    # Sequence-parallel execution (cfg.MESH.SEQ > 1, inside shard_map):
    # tokens are embedded redundantly per seq member, sliced to the local
    # shard, and the encoder runs with ring/Ulysses attention. Requires
    # pool='gap' (a broadcast class token has no single home shard).
    seq_axis: str | None = None
    seq_impl: str = "ring"

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        if self.pool not in ("token", "gap"):
            raise ValueError(f"pool must be 'token' or 'gap', got {self.pool!r}")
        if self.seq_axis is not None and self.pool != "gap":
            raise ValueError(
                "sequence-parallel ViT requires pool='gap': the class token "
                "has no home shard once tokens shard over the seq axis"
            )
        # [B, H, W, 3] -> [B, L, D]: non-overlapping patch conv (one big
        # [B·L, 3p²]×[3p², D] matmul after XLA's im2col — pure MXU work).
        x = nn.Conv(
            self.dim, (self.patch, self.patch),
            strides=(self.patch, self.patch), padding="VALID",
            dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=trunc_normal_02, name="patch_embed",
        )(x.astype(self.dtype))
        b = x.shape[0]
        x = x.reshape(b, -1, self.dim)
        if self.pool == "token":
            cls = self.param("cls_token", trunc_normal_02, (1, 1, self.dim), jnp.float32)
            x = jnp.concatenate([jnp.broadcast_to(cls, (b, 1, self.dim)).astype(x.dtype), x], axis=1)
        pos = self.param(
            "pos_embed", trunc_normal_02, (1, x.shape[1], self.dim), jnp.float32
        )
        x = x + pos.astype(x.dtype)

        if self.seq_axis is not None:
            # embedding ran redundantly per seq member (one cheap matmul);
            # slice the local token shard — the slice transpose zero-pads, so
            # patch-embed/pos grads stay PARTIAL and psum over seq is exact
            from distribuuuu_tpu.parallel.seq import local_tokens

            x = local_tokens(x, self.seq_axis)

        x = encode_tokens(
            x, depth=self.depth, num_heads=self.num_heads, mlp_dim=self.mlp_dim,
            dtype=self.dtype, remat=self.remat,
            seq_axis=self.seq_axis, seq_impl=self.seq_impl,
        )

        head = nn.Dense(
            self.num_classes, dtype=jnp.float32, param_dtype=jnp.float32,
            kernel_init=nn.initializers.zeros, name="head",
        )
        if self.seq_axis is not None:
            # Partial-sum pooling + the bias-1/P head: every parameter's
            # contribution stays member-partial so the trainer's uniform
            # seq-axis grad psum is exact. logits_i = W·(Σ_local x)/L + b/P
            # (the second head call contributes only -b·(P-1)/P — no W use),
            # and Σ_i logits_i = W·mean(x) + b, the dense head exactly. The
            # sum is psum_partial — partial values under a replicated
            # cotangent (parallel/seq.py), so grads stay exact partials.
            from distribuuuu_tpu.parallel.seq import psum_partial

            p = jax.lax.axis_size(self.seq_axis)
            l_global = x.shape[1] * p
            rep_partial = jnp.sum(x.astype(jnp.float32), axis=1) / l_global
            logits_partial = head(rep_partial) - (1.0 - 1.0 / p) * head(
                jnp.zeros_like(rep_partial)
            )
            return psum_partial(logits_partial, self.seq_axis)
        if self.pool == "token":
            rep = x[:, 0].astype(jnp.float32)
        else:
            rep = jnp.mean(x, axis=1, dtype=jnp.float32)
        return head(rep)


def encode_tokens(
    x: jnp.ndarray,
    *,
    depth: int,
    num_heads: int,
    mlp_dim: int,
    dtype: Any = jnp.bfloat16,
    remat: bool = False,
    seq_axis: str | None = None,
    seq_impl: str = "ring",
) -> jnp.ndarray:
    """Encoder stack over already-embedded tokens ``[B, L(_local), D]``.

    Position-agnostic by construction (positions are added at embed time),
    so under `shard_map` with tokens sharded over ``seq_axis`` every block
    is purely local EXCEPT the attention contraction, which ring/Ulysses
    makes exact across shards — the long-context execution mode
    (`parallel/ring_attention.py` module docstring). Must be called inside
    a module context (it creates the block submodules).
    """
    block_cls = maybe_remat(EncoderBlock, remat)
    for i in range(depth):
        x = block_cls(
            num_heads=num_heads, mlp_dim=mlp_dim, dtype=dtype,
            seq_axis=seq_axis, seq_impl=seq_impl, name=f"block{i}",
        )(x)
    return _layer_norm("ln_f")(x.astype(jnp.float32)).astype(x.dtype)


class ViTEncoder(nn.Module):
    """Bare encoder module over pre-embedded tokens — the unit the
    sequence-parallel path shard_maps (embedding/positions happen
    data-parallel upstream; see tests/test_models_vit.py)."""

    depth: int
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    remat: bool = False
    seq_axis: str | None = None
    seq_impl: str = "ring"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return encode_tokens(
            x, depth=self.depth, num_heads=self.num_heads, mlp_dim=self.mlp_dim,
            dtype=self.dtype, remat=self.remat,
            seq_axis=self.seq_axis, seq_impl=self.seq_impl,
        )


def _vit(patch, dim, depth, heads, mlp, **kw) -> ViT:
    kw.pop("zero_init_residual", None)  # resnet-family knob; meaningless here
    return ViT(patch=patch, dim=dim, depth=depth, num_heads=heads, mlp_dim=mlp, **kw)


@register_model("vit_s16")
def vit_s16(**kw):
    return _vit(16, 384, 12, 6, 1536, **kw)


@register_model("vit_b16")
def vit_b16(**kw):
    return _vit(16, 768, 12, 12, 3072, **kw)


@register_model("vit_l16")
def vit_l16(**kw):
    return _vit(16, 1024, 24, 16, 4096, **kw)
