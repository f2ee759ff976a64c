"""Shared building blocks for the model zoo.

Conventions (TPU-first):

- **NHWC** activations everywhere — the native layout for XLA:TPU conv
  emitters (the reference is NCHW because cuDNN prefers it; that would force
  transposes on TPU).
- Convs/dense run in the model's compute ``dtype`` (bfloat16 by default — full
  MXU rate); **parameters and BatchNorm statistics stay float32** and BN math
  is done in float32 for stability.
- Weight init matches torch semantics the reference relies on
  (`/root/reference/distribuuuu/models/resnet.py:213-228`): kaiming-normal
  fan-out for convs, unit/zero BN affine, with optional zero-γ on the last BN
  of a residual block ("zero-init-residual").
"""

from __future__ import annotations

from typing import Any, Callable, ClassVar

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

from distribuuuu_tpu.ops.epilogue import fused_conv_epilogue, switch_epilogue

# torch nn.init.kaiming_normal_(mode="fan_out", nonlinearity="relu"):
# N(0, sqrt(2 / fan_out)) — variance_scaling(2.0, fan_out, normal).
kaiming_normal_out = nn.initializers.variance_scaling(2.0, "fan_out", "normal")

# torch nn.Linear default: U(-1/sqrt(fan_in), 1/sqrt(fan_in)).
linear_uniform = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform")

# BN boundary (output) dtype for the whole zoo. float32 keeps every
# conv→BN→relu boundary in full precision but doubles the HBM bytes between
# conv stages and can split XLA fusions; bfloat16 is the MLPerf-era TPU
# recipe (statistics are STILL computed in float32 — flax upcasts half dtypes
# inside `_compute_stats` — and running stats/affine params stay float32;
# only the normalized activations are emitted in bf16). bf16 boundaries are
# +20% measured on resnet50/v5e. The trainer derives
# it from cfg.MODEL.BN_DTYPE ("auto" tracks MODEL.DTYPE) for the duration of
# train_model()/test_model() and restores the previous value on return, so
# direct build_model() calls outside a run keep the float32 default.
# Reading happens at *trace* time (batch_norm is called inside __call__), so
# the value in effect when a step is jitted is the one that binds; flipping
# it requires re-jitting. Process-global: concurrent runs in one process
# share it.
_BN_COMPUTE_DTYPE: Any = jnp.float32


def set_bn_compute_dtype(dtype: Any) -> None:
    global _BN_COMPUTE_DTYPE
    _BN_COMPUTE_DTYPE = dtype


def get_bn_compute_dtype() -> Any:
    return _BN_COMPUTE_DTYPE


def conv(
    features: int,
    kernel: int,
    stride: int = 1,
    *,
    padding: int | None = None,
    groups: int = 1,
    dtype: Any = jnp.bfloat16,
    name: str | None = None,
    kernel_init: Callable = kaiming_normal_out,
) -> nn.Conv:
    """Bias-free conv with torch-style *explicit symmetric* padding.

    Explicit numbers rather than "SAME": for even inputs and strided kernels
    SAME pads asymmetrically, which would silently misalign feature maps
    versus the reference recipe's conv arithmetic.
    """
    if padding is None:
        padding = (kernel - 1) // 2
    return nn.Conv(
        features=features,
        kernel_size=(kernel, kernel),
        strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        feature_group_count=groups,
        use_bias=False,
        dtype=dtype,
        param_dtype=jnp.float32,
        kernel_init=kernel_init,
        name=name,
    )


def batch_norm(
    *,
    train: bool,
    axis_name: str | None = None,
    zero_scale: bool = False,
    name: str | None = None,
    momentum: float = 0.9,
    epsilon: float = 1e-5,
) -> nn.BatchNorm:
    """BatchNorm matching torch defaults (eps 1e-5, momentum 0.1 ⇒ flax 0.9).

    ``axis_name='data'`` turns this into SyncBN: batch statistics are averaged
    across the mesh's data axis with `lax.pmean` inside the shard_mapped step —
    the XLA-collective replacement for `nn.SyncBatchNorm.convert_sync_batchnorm`
    (`/root/reference/distribuuuu/trainer.py:131`).

    Statistics are always computed in float32; the module-level
    :data:`_BN_COMPUTE_DTYPE` (single source of truth — see the note above
    `set_bn_compute_dtype`) only controls the emitted activation dtype.
    """
    return nn.BatchNorm(
        use_running_average=not train,
        momentum=momentum,
        epsilon=epsilon,
        dtype=_BN_COMPUTE_DTYPE,
        param_dtype=jnp.float32,
        axis_name=axis_name,
        scale_init=nn.initializers.zeros if zero_scale else nn.initializers.ones,
        name=name,
    )


class EpilogueBatchNorm(nn.BatchNorm):
    """`nn.BatchNorm` whose *apply* is the fused conv-epilogue kernel.

    The fused route of :func:`bn_epilogue`. Statistics stay exactly flax's
    code — the same `_compute_stats` (f32 reductions, fast variance, the
    SyncBN ``pmean`` over ``axis_name``) and the same running-EMA update —
    so SyncBN and ``MODEL.BN_DTYPE`` semantics are untouched; only the
    per-element normalize → (+residual) → ReLU tail runs through
    `ops.epilogue.fused_conv_epilogue` (which folds the stats to the same
    ``mean``/``rsqrt(var+eps)·scale``/``bias`` affine ``_normalize`` applies,
    in the same operation order — bitwise-equal output, pinned in
    tests/test_epilogue.py).

    A subclass rather than a sibling so variable paths (``scale``/``bias``
    params, ``mean``/``var`` batch_stats under the same module name) are
    identical — checkpoints trained fused load unfused and vice versa.
    """

    relu: bool = True
    # PTQ fold detection (quant/ptq.py) must NOT treat this module as a
    # plain BN: its call also applies the residual add and the ReLU, so
    # substituting the BN-fold affine/identity for it would drop both —
    # the site stays a live op (exactly what fused routing executes)
    fused_epilogue: ClassVar[bool] = True

    @nn.compact
    def __call__(self, x, identity=None, use_running_average=None):  # noqa: D102
        # private flax helpers, imported HERE so a flax release moving them
        # breaks only this opt-in fused path, not `import models.layers`
        from flax.linen import dtypes as _flax_dtypes
        from flax.linen.normalization import _compute_stats

        if self.axis != -1 or not (self.use_scale and self.use_bias):
            raise NotImplementedError(
                "EpilogueBatchNorm supports the zoo's BN shape only "
                "(axis=-1, affine scale+bias)"
            )
        use_running_average = nn.merge_param(
            "use_running_average", self.use_running_average, use_running_average
        )
        feature_shape = [x.shape[-1]]
        reduction_axes = tuple(range(x.ndim - 1))
        ra_mean = self.variable(
            "batch_stats", "mean", lambda s: jnp.zeros(s, jnp.float32), feature_shape
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda s: jnp.ones(s, jnp.float32), feature_shape
        )
        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            mean, var = _compute_stats(
                x,
                reduction_axes,
                dtype=self.dtype,
                axis_name=self.axis_name if not self.is_initializing() else None,
                axis_index_groups=self.axis_index_groups,
                use_fast_variance=self.use_fast_variance,
                force_float32_reductions=self.force_float32_reductions,
            )
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value + (1 - self.momentum) * mean
                )
                ra_var.value = self.momentum * ra_var.value + (1 - self.momentum) * var
        scale = self.param("scale", self.scale_init, feature_shape, self.param_dtype)
        bias = self.param("bias", self.bias_init, feature_shape, self.param_dtype)
        # the affine _normalize folds to, in its operation order: rsqrt
        # first, then the scale multiply (association changes bits)
        mul = lax.rsqrt(var + self.epsilon) * scale
        bn_dtype = _flax_dtypes.canonicalize_dtype(x, scale, bias, dtype=self.dtype)
        return fused_conv_epilogue(
            x, mean, mul, bias, identity, relu=self.relu, bn_dtype=bn_dtype
        )


def bn_epilogue(
    x: jnp.ndarray,
    *,
    train: bool,
    axis_name=None,
    zero_scale: bool = False,
    identity: jnp.ndarray | None = None,
    relu: bool = True,
    name: str,
    momentum: float = 0.9,
    epsilon: float = 1e-5,
) -> jnp.ndarray:
    """The conv-epilogue: BN → (+``identity``) → ReLU, routed fused/unfused.

    The unfused default is *literally* the pre-existing block code
    (`batch_norm` + add + `nn.relu`) — zero semantic change when
    `ops.epilogue.switch_epilogue` says off (the shipping default). Fused
    (``MODEL.FUSED_EPILOGUE``) swaps in
    :class:`EpilogueBatchNorm` under the same module ``name``, so the
    variable tree — and therefore checkpoints, the torch converter, and
    pretrained loading — is identical either way.
    """
    if not switch_epilogue():
        y = batch_norm(
            train=train,
            axis_name=axis_name,
            zero_scale=zero_scale,
            name=name,
            momentum=momentum,
            epsilon=epsilon,
        )(x)
        if identity is not None:
            y = y + identity
        return nn.relu(y) if relu else y
    return EpilogueBatchNorm(
        use_running_average=not train,
        momentum=momentum,
        epsilon=epsilon,
        dtype=_BN_COMPUTE_DTYPE,
        param_dtype=jnp.float32,
        axis_name=axis_name,
        scale_init=nn.initializers.zeros if zero_scale else nn.initializers.ones,
        relu=relu,
        name=name,
    )(x, identity)


def classifier_head(x: jnp.ndarray, num_classes: int, *, name: str = "fc") -> jnp.ndarray:
    """Global average pool (NHWC spatial axes) + float32 linear classifier."""
    x = jnp.mean(x, axis=(1, 2), dtype=jnp.float32)
    return nn.Dense(
        num_classes,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        kernel_init=linear_uniform,
        bias_init=nn.initializers.zeros,
        name=name,
    )(x)


class SqueezeExcite(nn.Module):
    """SE gate: GAP → 1×1 reduce → act → 1×1 expand → sigmoid·x.

    Shared by EfficientNet (SiLU) and RegNetY (ReLU); the reduce dim is
    computed by the caller (both families size it from the block's *input*
    channels, not the gated tensor's).
    """

    se_dim: int
    act: Callable = nn.relu
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        s = jnp.mean(x, axis=(1, 2), keepdims=True, dtype=jnp.float32).astype(x.dtype)
        s = nn.Conv(self.se_dim, (1, 1), dtype=self.dtype, param_dtype=jnp.float32, name="reduce")(s)
        s = self.act(s)
        s = nn.Conv(x.shape[-1], (1, 1), dtype=self.dtype, param_dtype=jnp.float32, name="expand")(s)
        return x * nn.sigmoid(s)


def maybe_remat(module_cls, enabled: bool):
    """`jax.checkpoint` a block class — the `torch.utils.checkpoint` analog the
    reference uses for memory-efficient DenseNet (`densenet.py:81-108`),
    generalized to every family via cfg.MODEL.REMAT."""
    return nn.remat(module_cls) if enabled else module_cls
