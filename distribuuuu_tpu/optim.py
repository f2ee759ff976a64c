"""Optimizer and epoch-granular LR schedules.

Semantics-parity notes versus the reference:

- **SGD update rule** (`/root/reference/distribuuuu/utils.py:187-196`, torch
  SGD): ``g = grad + wd·p``; ``buf = m·buf + (1-dampening)·g``; update is
  ``g + m·buf`` under nesterov else ``buf``; then ``p -= lr·update``. The LR
  multiplies the update *after* momentum, so the buffer is LR-free — the
  optimizer chain here therefore excludes LR, and the trainer applies
  ``-lr`` at update time with lr passed as a traced scalar (changing it per
  epoch never recompiles the step).
- **Weight decay is coupled L2 on every parameter** (torch default: a single
  param group), including BN affine and biases — kept for baseline parity.
- **Schedules are epoch-granularity** (`trainer.py:25-26`): LR is computed on
  the host once per epoch with *exactly* the reference math
  (`utils.py:280-310`): cosine ``(1-MIN_LR)·½(1+cos(πe/E)) + MIN_LR`` scaled
  by BASE_LR; steps ``LR_MULT^(last index with e ≥ STEPS[i])``; linear warmup
  factor ``WARMUP_FACTOR·(1-α)+α`` with ``α = e/WARMUP_EPOCHS``.
"""

from __future__ import annotations

from typing import NamedTuple

import chex
import jax
import jax.numpy as jnp
import numpy as np
import optax

from distribuuuu_tpu.config import cfg


# ---------------------------------------------------------------------------
# LR schedule (host-side, float math identical to reference)
# ---------------------------------------------------------------------------

def lr_fun_steps(cur_epoch: int) -> float:
    """Steps schedule (cfg.OPTIM.LR_POLICY = 'steps')."""
    ind = [i for i, s in enumerate(cfg.OPTIM.STEPS) if cur_epoch >= s][-1]
    return cfg.OPTIM.LR_MULT**ind


def lr_fun_cos(cur_epoch: int) -> float:
    """Half-period cosine schedule (cfg.OPTIM.LR_POLICY = 'cos')."""
    lr = 0.5 * (1.0 + np.cos(np.pi * cur_epoch / cfg.OPTIM.MAX_EPOCH))
    return (1.0 - cfg.OPTIM.MIN_LR) * lr + cfg.OPTIM.MIN_LR


_LR_POLICIES = {"steps": lr_fun_steps, "cos": lr_fun_cos}

#: adafactor: a leaf's second moment is factored over its two largest axes
#: where both have at least this many entries (optax's own default)
FACTOR_MIN_DIM = 128


def get_epoch_lr(cur_epoch: int) -> float:
    """LR for a given epoch: policy × BASE_LR, with linear warmup."""
    try:
        lr_fun = _LR_POLICIES[cfg.OPTIM.LR_POLICY]
    except KeyError:
        raise ValueError(f"Unknown LR policy: {cfg.OPTIM.LR_POLICY}") from None
    lr = lr_fun(cur_epoch) * cfg.OPTIM.BASE_LR
    if cur_epoch < cfg.OPTIM.WARMUP_EPOCHS:
        alpha = cur_epoch / cfg.OPTIM.WARMUP_EPOCHS
        warmup_factor = cfg.OPTIM.WARMUP_FACTOR * (1.0 - alpha) + alpha
        lr *= warmup_factor
    return lr


# ---------------------------------------------------------------------------
# SGD transform (LR-free; trainer scales by -lr)
# ---------------------------------------------------------------------------

class TraceState(NamedTuple):
    momentum: optax.Updates
    step: chex.Array


def sgd_momentum(
    momentum: float, dampening: float = 0.0, nesterov: bool = True
) -> optax.GradientTransformation:
    """Torch-semantics momentum (supports dampening, unlike `optax.trace`).

    Torch seeds the buffer with the *raw* first gradient (``buf = g``, not
    ``(1-dampening)·g``); a step counter reproduces that exactly while keeping
    the state pytree structure static for jit.
    """

    def init(params):
        return TraceState(
            momentum=jax.tree.map(jnp.zeros_like, params),
            step=jnp.zeros((), jnp.int32),
        )

    def update(updates, state, params=None):
        del params
        first = state.step == 0

        def upd(g, buf):
            seeded = jnp.where(first, g, momentum * buf + (1.0 - dampening) * g)
            return seeded

        new_bufs = jax.tree.map(upd, updates, state.momentum)
        if nesterov:
            outs = jax.tree.map(lambda g, b: g + momentum * b, updates, new_bufs)
        else:
            outs = new_bufs
        return outs, TraceState(momentum=new_bufs, step=state.step + 1)

    return optax.GradientTransformation(init, update)


def _scale_by_trust_ratio_fsdp(
    param_specs, fsdp_axis: str
) -> optax.GradientTransformation:
    """`optax.scale_by_trust_ratio` for fsdp-sharded leaves.

    The trust ratio is the one LAMB stage that is not leafwise-elementwise:
    it needs each parameter's (and update's) *global* L2 norm, and on a
    1/N shard a local norm is wrong. For leaves ``param_specs`` marks as
    sharded, the squared norm is ``psum``'d over the fsdp axis before the
    sqrt; replicated leaves (identical on every fsdp rank once grads are
    averaged) use their local norm unchanged. Same formula as optax 0.2.x
    (trust_coefficient=1, eps=0, min_norm=0): ratio = |p|/|u|, 1 where
    either norm is zero. Must be applied under a `shard_map` that has the
    fsdp axis in scope.
    """
    from distribuuuu_tpu.parallel import fsdp as _fsdp

    def _norm(x, spec):
        sq = jnp.sum(jnp.square(x))
        if _fsdp.fsdp_dim(spec) is not None:
            sq = jax.lax.psum(sq, fsdp_axis)
        return jnp.sqrt(sq)

    def init(params):
        del params
        return optax.EmptyState()

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("trust ratio needs params")

        def one(u, p, spec):
            p_norm = _norm(p, spec)
            u_norm = _norm(u, spec)
            zero = jnp.logical_or(p_norm == 0.0, u_norm == 0.0)
            ratio = jnp.where(
                zero, jnp.array(1.0, dtype=p.dtype), p_norm / u_norm
            )
            return u * ratio

        return jax.tree.map(one, updates, params, param_specs), state

    return optax.GradientTransformation(init, update)


def construct_optimizer(
    param_specs=None, fsdp_axis: str | None = None
) -> optax.GradientTransformation:
    """Build the cfg-selected optimizer as an LR-free ascent direction; the
    trainer applies ``params - lr·update`` with lr as a traced scalar.

    - ``sgd`` (default): torch-exact SGD+momentum+nesterov+coupled-WD
      (reference `utils.py:187-196`).
    - ``adafactor``: factored second moment, no first (Shazeer & Stern 2018)
      — for models whose parameters fill most of a chip.
    - ``lamb``: layerwise-adaptive large-batch optimizer (You et al. 2020) —
      beyond the reference, whose large-batch story stops at SGD + linear LR
      scaling (`README.md:174-192`); LAMB is the standard recipe for pushing
      ImageNet global batches past ~8k on big TPU meshes. Composed of the
      same optax primitives as `optax.lamb`, minus the final ``scale(-lr)``
      (the trust ratio is LR-independent, so the epoch-LR contract holds).

    Under fsdp (``param_specs`` + ``fsdp_axis`` set by
    `trainer.create_train_state` when cfg.MESH.FSDP > 1) the update runs on
    the 1/N *shard*: every SGD stage (coupled WD, the momentum buffer, the
    nesterov combine) is leafwise-elementwise, so shard-in/shard-out is the
    identical math on a slice — momentum lives sharded exactly like its
    parameter. LAMB's trust ratio is the one norm-based stage and swaps in
    the fsdp-aware variant above.
    """
    name = cfg.OPTIM.OPTIMIZER
    if name == "sgd":
        return optax.chain(
            optax.add_decayed_weights(cfg.OPTIM.WEIGHT_DECAY),
            sgd_momentum(
                momentum=cfg.OPTIM.MOMENTUM,
                dampening=cfg.OPTIM.DAMPENING,
                nesterov=cfg.OPTIM.NESTEROV,
            ),
        )
    # Weight decay masked to multi-dim params: published large-batch LAMB
    # recipes exclude biases and BN scale/shift from decay (unlike the
    # SGD branch, where decay-everything IS the torch reference parity).
    def _wd_mask(params):
        return jax.tree.map(lambda p: p.ndim > 1, params)

    if name == "lamb":
        # The trust ratio stays optax-canonical (unmasked) — for 1-D params
        # scale_by_trust_ratio already degenerates gracefully.
        if param_specs is not None and fsdp_axis is not None:
            trust = _scale_by_trust_ratio_fsdp(param_specs, fsdp_axis)
        else:
            trust = optax.scale_by_trust_ratio()
        return optax.chain(
            optax.scale_by_adam(
                b1=cfg.OPTIM.BETA1, b2=cfg.OPTIM.BETA2, eps=cfg.OPTIM.EPS
            ),
            optax.add_decayed_weights(cfg.OPTIM.WEIGHT_DECAY, mask=_wd_mask),
            trust,
        )
    if name == "adafactor":
        if param_specs is not None:
            raise ValueError(
                "OPTIM.OPTIMIZER 'adafactor' keeps row/column statistics and a "
                "block RMS of whole leaves: not under MESH.FSDP > 1"
            )
        # Shazeer & Stern 2018 as `optax.adafactor` composes it, minus the
        # learning rate (the trainer's) and the optional momentum: the
        # factored second moment (decay 1 - t^-0.8), the update clipped to
        # unit RMS a leaf, scaled by the leaf's own RMS (at least 1e-3), then
        # LAMB's masked decoupled decay. The state is a row and a column
        # vector a matrix: a few MB beside a model of GBs.
        return optax.chain(
            optax.scale_by_factored_rms(min_dim_size_to_factor=FACTOR_MIN_DIM),
            optax.clip_by_block_rms(1.0),
            optax.scale_by_param_block_rms(),
            optax.add_decayed_weights(cfg.OPTIM.WEIGHT_DECAY, mask=_wd_mask),
        )
    raise ValueError(
        f"Unknown OPTIM.OPTIMIZER {name!r} (available: 'sgd', 'lamb', 'adafactor')"
    )


def apply_updates_with_lr(params, updates, lr: chex.Numeric):
    """``p ← p − lr·u`` with lr a traced scalar (no recompile across epochs)."""
    return jax.tree.map(lambda p, u: (p - lr * u).astype(p.dtype), params, updates)
