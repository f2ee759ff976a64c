"""Plain reference: SGD with momentum, Nesterov, coupled L2 weight decay (torch semantics).

``g = grad + wd * p``; the buffer starts as the first ``g`` and then is
``m * buf + g``; the update is ``g + m * buf`` (Nesterov); ``p -= lr * update``.
Flat dicts of float32 arrays in, flat dicts out. Imports nothing of the program.
"""

from __future__ import annotations

import jax.numpy as jnp


def init(params: dict) -> dict:
    return {"step": jnp.zeros((), jnp.int32), "buf": {k: jnp.zeros_like(v) for k, v in params.items()}}


def step(params: dict, state: dict, grads: dict, lr, hp: dict):
    wd, m = float(hp["WEIGHT_DECAY"]), float(hp["MOMENTUM"])
    if float(hp.get("DAMPENING", 0.0)) != 0.0 or not hp.get("NESTEROV", True):
        raise ValueError("reference SGD covers the shipped recipe only: Nesterov, no dampening")
    new_params, new_buf = {}, {}
    for k, p in params.items():
        g = grads[k] + wd * p
        buf = jnp.where(state["step"] == 0, g, m * state["buf"][k] + g)
        new_buf[k] = buf
        new_params[k] = p - lr * (g + m * buf)
    return new_params, {"step": state["step"] + 1, "buf": new_buf}


def first_gradient(program_opt_state, program_params0, hp: dict):
    """The first gradient as the program's optimizer got it, from its state after one step.

    The program's state is an optax chain ``(decay, trace)``; after one step the
    trace's momentum is ``grad + wd * p0``.
    """
    import jax

    wd = float(hp["WEIGHT_DECAY"])
    momentum = program_opt_state[1].momentum
    return jax.tree.map(lambda buf, p: buf - wd * p, momentum, program_params0)
