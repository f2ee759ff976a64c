"""Plain reference: Adafactor (Shazeer & Stern 2018) as the shipped recipe composes it.

No first moment. The second moment of a leaf whose two largest axes both have at least
``FACTOR_MIN_DIM`` entries is kept as the means of ``g² + 1e-30`` over each of those two axes
(``v_row``: the largest axis reduced; ``v_col``: the second largest), else whole; decay
``1 - t^-0.8`` (so the first step keeps ``g²`` itself). The update ``g / sqrt(v)`` (factored:
``g · (v_row / mean(v_row))^-½ · v_col^-½``) is clipped to unit root-mean-square a leaf, scaled by
the leaf's own root-mean-square (at least 1e-3), decoupled weight decay is added on leaves of more
than one dimension, then ``p -= lr * u``. Flat dicts of float32 arrays. Imports nothing of the
program.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

EPS_GRAD, MIN_SCALE, CLIP, DECAY_EXPONENT, FACTOR_MIN_DIM = 1e-30, 1e-3, 1.0, 0.8, 128


def factored_axes(shape):
    """(second largest, largest) axis of a leaf that is factored, else None."""
    if len(shape) < 2:
        return None
    order = np.argsort(shape)
    if shape[order[-2]] < FACTOR_MIN_DIM:
        return None
    return int(order[-2]), int(order[-1])


def _zeros(p) -> dict:
    axes = factored_axes(p.shape)
    if axes is None:
        return {"v": jnp.zeros_like(p)}
    d1, d0 = axes
    return {"v_row": jnp.zeros(np.delete(p.shape, d0), p.dtype), "v_col": jnp.zeros(np.delete(p.shape, d1), p.dtype)}


def init(params: dict) -> dict:
    return {"step": jnp.zeros((), jnp.int32), "v": {k: _zeros(p) for k, p in params.items()}}


def step(params: dict, state: dict, grads: dict, lr, hp: dict):
    wd = float(hp["WEIGHT_DECAY"])
    decay = 1.0 - (state["step"] + 1).astype(jnp.float32) ** -DECAY_EXPONENT
    rms = lambda t: jnp.sqrt(jnp.mean(jnp.square(t)))
    new_params, new_v = {}, {}
    for k, p in params.items():
        g, v = grads[k], state["v"][k]
        sq = jnp.square(g) + EPS_GRAD
        axes = factored_axes(p.shape)
        if axes is None:
            new_v[k] = {"v": decay * v["v"] + (1 - decay) * sq}
            u = g * new_v[k]["v"] ** -0.5
        else:
            d1, d0 = axes
            row = decay * v["v_row"] + (1 - decay) * jnp.mean(sq, axis=d0)
            col = decay * v["v_col"] + (1 - decay) * jnp.mean(sq, axis=d1)
            new_v[k] = {"v_row": row, "v_col": col}
            row_mean = jnp.mean(row, axis=d1 - 1 if d1 > d0 else d1, keepdims=True)
            u = g * jnp.expand_dims((row / row_mean) ** -0.5, d0) * jnp.expand_dims(col ** -0.5, d1)
        u = u / jnp.maximum(1.0, rms(u) / CLIP)
        u = u * jnp.maximum(rms(p), MIN_SCALE)
        if p.ndim > 1:
            u = u + wd * p
        new_params[k] = p - lr * u
    return new_params, {"step": state["step"] + 1, "v": new_v}


def first_gradient(program_opt_state, program_params0, hp: dict):
    """Per leaf an array whose norm is the first gradient's, from the program's state after one step.

    The first step's decay is 0, so the second moment *is* ``g² + 1e-30`` (its mean over the
    largest axis where factored): ``sqrt(v)``, or ``sqrt(v_row · that axis' length)``, has the
    gradient's norm. The state is an optax chain whose first member holds ``v_row`` / ``v`` as trees of
    the parameters' structure (a one-entry placeholder where the other form is used).
    """
    import jax

    del hp
    factored = program_opt_state[0]

    def one(p, v_row, v):
        axes = factored_axes(p.shape)
        return jnp.sqrt(v) if axes is None else jnp.sqrt(v_row * p.shape[axes[1]])

    return jax.tree.map(one, program_params0, factored.v_row, factored.v)
