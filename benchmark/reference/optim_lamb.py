"""Plain reference: LAMB (You et al. 2020) as the shipped recipe composes it.

Adam moments with bias correction (eps added outside the root), decoupled
weight decay on leaves of more than one dimension, the layer-wise trust ratio
``|p| / |u|`` (1 where either norm is 0), then ``p -= lr * ratio * u``.
Flat dicts of float32 arrays. Imports nothing of the program.
"""

from __future__ import annotations

import jax.numpy as jnp


def init(params: dict) -> dict:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"step": jnp.zeros((), jnp.int32), "mu": zeros, "nu": dict(zeros)}


def step(params: dict, state: dict, grads: dict, lr, hp: dict):
    b1, b2, eps, wd = (float(hp[k]) for k in ("BETA1", "BETA2", "EPS", "WEIGHT_DECAY"))
    t = state["step"] + 1
    tf = t.astype(jnp.float32)
    new_params, mu, nu = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        mu[k] = b1 * state["mu"][k] + (1 - b1) * g
        nu[k] = b2 * state["nu"][k] + (1 - b2) * jnp.square(g)
        u = (mu[k] / (1 - b1**tf)) / (jnp.sqrt(nu[k] / (1 - b2**tf)) + eps)
        if p.ndim > 1:
            u = u + wd * p
        p_norm, u_norm = jnp.linalg.norm(p.ravel()), jnp.linalg.norm(u.ravel())
        ratio = jnp.where((p_norm == 0) | (u_norm == 0), 1.0, p_norm / u_norm)
        new_params[k] = p - lr * ratio * u
    return new_params, {"step": t, "mu": mu, "nu": nu}


def first_gradient(program_opt_state, program_params0, hp: dict):
    """After one step the program's first Adam moment is ``(1 - b1) * grad``."""
    import jax

    b1 = float(hp["BETA1"])
    return jax.tree.map(lambda mu: mu / (1 - b1), program_opt_state[0].mu)
